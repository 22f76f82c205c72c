"""One module for each ``kind`` of configuration; ``run(job)`` returns the
window's record, which the readers turn into metrics."""
