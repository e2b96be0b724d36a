"""The benchmark's yardstick, frozen so that no later PR can move it.

- ``oracle.py`` / ``config.py``: copies of ``semantics/oracle.py`` and the
  constants and policy arithmetic it takes from ``core/config.py``.
- ``groups.py``: the same semantics applied to a whole call at once
  (every request of a call carries the call's timestamp), vectorised over
  keys; ``tests/test_reference.py`` holds it equal to ``oracle.py``.

Nothing here imports the program.
"""
