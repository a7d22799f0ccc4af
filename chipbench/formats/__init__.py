"""Formats of the linear weights, one module each, named by a
configuration's ``format.kind``. Each module gives:

``make_layer(key, shapes, fmt)``   one layer's linears from its key, on
                                   the device, as the format stores them
``program_linears(make, n_layers, fmt, dtype)``
                                   the program's stacked per-path leaves,
                                   built one layer at a time
``dense_equivalent(part)``         one linear as the float32 (d_in, d_out)
                                   matrix the reference multiplies by
"""
