"""vbench: the benchmark of the serving data plane (see vbench/README.md).

Everything that decides a number lives here, where later PRs may add files
but not edit them: the traffic generator, the client that stamps tokens,
the weights made from the seed, the plain references, the comparison that
decides ``correct``, the trace reduction, the cost functions and the table
of peaks. From the program it takes only ``ServingEngine`` with its slot
models, ``stats()`` and the names the device trace gives.
"""
