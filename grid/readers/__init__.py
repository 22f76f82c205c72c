"""Per-layer and end-to-end metrics, one small function each:
``reader(record, trace) -> number or None``. ``record`` is what the driver
measured in the window; ``trace`` is the reduced profiler trace of a traced
run (``grid/reduce.Trace``), else None. A metric's file names its function
as ``"<module>.<function>"``; a later PR adds a module, never edits one."""
