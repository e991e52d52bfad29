"""The on-chip benchmark of the DHash table service (``python3 -m bench.run``).

Everything that decides what a cell measures lives here: the traffic
generator, the host reference model, the client loop, the trace reduction,
the peaks table and the necessary-bytes arithmetic.  The program under
``src/`` supplies only the system under test.
"""
