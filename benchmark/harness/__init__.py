"""The benchmark's own code: everything a cell needs except the system
under test.  ``run.py`` finds a cell's configuration, traffic and
per-layer readers by name; the driver named by the traffic file
(``harness/<driver>.py``) runs it."""
