"""``correct`` of the transfer cells, driven through the whole harness at
a tiny size on the CPU (the look for a chip skipped): sound runs pass; the
control (a plain copy one precision down) and each planted fault of the
timed path come out not correct."""
import time

import numpy as np
import pytest

from bench import harness


def _run(root, cell, seed=2 ** 31 + 5, control=False):
    return harness.run_cell(root, cell, seed, 0.3, False,
                            t_start=time.perf_counter(), require_chip=False,
                            control=control, log=lambda m: None)


@pytest.mark.parametrize("cell", ["tiny-ssm.stage", "tiny-hybrid.resume"])
def test_sound_runs_are_correct(tiny_root, cell):
    out = _run(tiny_root, cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"pass_ms", "setup_s"}
    assert out["checks"]["passes_mismatched"] == {"value": 0, "limit": 0}


@pytest.mark.parametrize("cell", ["tiny-ssm.stage", "tiny-hybrid.resume"])
def test_the_control_is_not_correct(tiny_root, cell):
    out = _run(tiny_root, cell, control=True)
    assert not out["correct"]
    assert out["checks"]["passes_mismatched"]["value"] == out["attempted"]
    assert out["checks"]["leaves_mismatched"]["value"] > 0


def test_an_answer_altered_where_it_is_produced(tiny_root, monkeypatch):
    """One byte of the largest staging bucket flips after every pack."""
    from repro.core import engine

    real = engine.ArenaEntry.pack_host

    def pack_host(self, tree, **kw):
        buffers = real(self, tree, **kw)
        big = max(buffers, key=lambda b: buffers[b].nbytes)
        buffers[big].view(np.uint8)[7] ^= 0x10
        return buffers

    monkeypatch.setattr(engine.ArenaEntry, "pack_host", pack_host)
    out = _run(tiny_root, "tiny-ssm.stage")
    assert not out["correct"]
    assert out["checks"]["passes_mismatched"]["value"] > 0


def test_a_pass_that_leaves_the_state_unchanged(tiny_root, monkeypatch):
    """After its first pass the program hands back that pass's tree: the
    sessions resumed later never reach the device."""
    from repro.core.policy import TransferProgram

    real = TransferProgram.to_device
    first = {}

    def to_device(self, tree):
        if id(self) not in first:
            first[id(self)] = real(self, tree)
        return first[id(self)]

    monkeypatch.setattr(TransferProgram, "to_device", to_device)
    out = _run(tiny_root, "tiny-hybrid.resume")
    assert not out["correct"]
    assert out["checks"]["passes_mismatched"]["value"] > 0
