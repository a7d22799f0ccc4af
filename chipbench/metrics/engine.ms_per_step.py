"""engine.ms_per_step: host-clock milliseconds per engine step over the
traced window, its length over the steps ``Engine.n_steps`` advanced in
it. Layer: serving/engine. Moves itl_p50_ms."""


def read(run):
    if run.window_steps <= 0:
        return None
    return 1e3 * run.window_s / run.window_steps
