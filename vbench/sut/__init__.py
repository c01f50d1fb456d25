"""The system under test, one builder a model family: the only modules of
the benchmark that import the program. Each hands the benchmark's own
weights to the program's own slot model and returns a ``ServingEngine``
built as a deployment would build it."""
