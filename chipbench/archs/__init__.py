"""Architectures, one module each, named by a configuration's ``arch``.
Each module gives the program's configuration and parameter tree for a
configuration file, and the plain float32 reference forward pass that
decides ``correct``. The reference imports nothing of the program."""
