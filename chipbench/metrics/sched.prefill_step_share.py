"""sched.prefill_step_share: percent of the traced window's steps whose plan fed
more than one position (some row was prefilling, so every decode row
waited for the whole chunk). Counted by the benchmark's wrapper on the
scheduler's ``plan_step``. Layer: serving/scheduler. Moves itl_p95_ms."""


def read(run):
    if not run.plans:
        return None
    return 100.0 * sum(p.c > 1 for p in run.plans) / len(run.plans)
