"""The comparison catches a broken timed path: a run driven past the
look for a chip, at test widths on the CPU, with the engine's decode
step broken underneath, reads `correct` false; the same run unbroken
reads true, for each fault of faults.py."""
import time

import pytest

import faults
import tiny

# the test cells' limit: at these widths unbroken runs read 0.00-0.21 over
# seeds 1-6, a state left unchanged 0.39-1.97, an altered token 2.0-3.8
LIMIT = 0.3


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make(str(tmp_path_factory.mktemp("bench")), limit=LIMIT)


@pytest.mark.parametrize("name", ["tiny.decode", "tiny.chat"])
@pytest.mark.parametrize("fault,correct", [
    (None, True), (faults.state_unchanged, False),
    (faults.token_altered, False)])
def test_fault_reads_incorrect(root, name, fault, correct):
    import harness
    c = tiny.load(root, name)
    r = harness.run(c, seed=2, seconds=2.5, trace=False,
                    t_start=time.perf_counter(), arch_override=tiny.arch(),
                    fault=fault)
    gap = r["checks"]["max_logit_gap"]["value"]
    assert r["correct"] is correct, gap
