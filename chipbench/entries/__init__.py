"""The engine calls a cell can time, one module each, found by the
configuration's ``"entry"`` (``run.entry_module``)."""
