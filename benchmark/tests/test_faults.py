"""Each fault a one-chip cell can have, planted under the timed path of
a tiny run with the harness's look for a chip skipped, turns ``correct``
false.  (The exchange between chips does not exist on one chip.)

- ``state_unchanged``: every decision sees the state as it was before
  the run (a step that returns its state unchanged);
- ``half_left_out``: half of the requests never reach the limiter and
  are answered "allowed";
- ``answer_altered``: one answer flipped where it is produced.
"""

import numpy as np
import pytest

from harness import stream
from tiny import run_tiny, tiny_cell

FAULTS = ["state_unchanged", "half_left_out", "answer_altered"]


class FaultyLimiter:
    def __init__(self, storage, limiter, fault):
        self.storage, self.real, self.fault = storage, limiter, fault

    def try_acquire_stream_ids(self, ids, **kw):
        if self.fault == "state_unchanged":
            fresh = type(self.real)(self.storage, self.real._config,
                                    _registry())
            return fresh.try_acquire_stream_ids(ids, **kw)
        if self.fault == "half_left_out":
            half = len(ids) // 2
            got = self.real.try_acquire_stream_ids(ids[:half], **kw)
            return np.concatenate([got, np.ones(len(ids) - half, bool)])
        got = np.array(self.real.try_acquire_stream_ids(ids, **kw))
        got[len(got) // 3] ^= True
        return got


def _registry():
    from ratelimiter_tpu.metrics import MeterRegistry

    return MeterRegistry()


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("name", ["tb-burst-1m-zipf.stream",
                                  "sw-api-10m-uniform.stream"])
def test_stream_fault_is_not_correct(name, fault):
    def build(config, clock):
        storage, limiter = stream.build_limiter(config, clock)
        return storage, FaultyLimiter(storage, limiter, fault)

    out = run_tiny(tiny_cell(name), build=build)
    assert not out.correct, (fault, out.checks)
