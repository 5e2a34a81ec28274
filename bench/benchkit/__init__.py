"""The benchmark's own library: the harness, the load generator's
process, a wire client, fleet signals, the plain IDEALEM reference, trace
reduction and roofline counts.  Each traffic kind's generator and
comparison lives in ``bench/kinds/<kind>.py``, found by name.

Nothing here imports the program under test except ``server`` (which runs
it) and nothing here imports JAX except ``server``, ``compiles`` and
``xplane``; the load generator and the reference stay free of both.
"""
