"""A cell of the benchmark cut to a size the CPU runs in seconds, and the
faults a run can have, planted underneath the timed path. Shared by the
driver tests."""

from __future__ import annotations

import dataclasses

import numpy as np

from harness import spec

# Limits for these sizes on the CPU, set as the cells' own are from chip
# readings: sound runs of the periodic cell at this size read loss gaps of
# 1.6e-5 to 3.7e-5, grad gaps of 4.7e-4 to 7.9e-4 and change gaps of
# 2.0e-3 to 2.2e-3 (seeds 1 to 3); the float8 control reads 2.1e-4 to
# 4.0e-4, 1.01e-2 to 1.09e-2 and 6.5e-3 to 1.05e-2. bfloat16 on the CPU
# rounds in other places than the chip does, so the chip's limits do not
# carry over to this size.
CPU_LIMITS = {"loss_gap": 1e-4, "grad_gap": 3e-3, "change_gap": 5e-3}


def smoke_cell(name: str, *, limits=None) -> spec.Cell:
    """The cell with its configuration cut by its program module's
    ``smoke``."""
    cell = spec.load_cell(name)
    cfg = spec.program_module(cell.config).smoke(cell.config)
    traffic = dict(cell.traffic)
    if "min_steps_after_save" in traffic:
        traffic["min_steps_after_save"] = 6
    return dataclasses.replace(cell, config=cfg, traffic=traffic,
                               limits=dict(CPU_LIMITS if limits is None
                                           else limits))


def _step_factory(real, fault: str):
    def factory(*args, **kwargs):
        step = real(*args, **kwargs)
        if fault == "unchanged":
            def broken(state, batch):
                _new, metrics = step(state, batch)
                return state, metrics
        elif fault == "half_batch":
            def broken(state, batch):
                return step(state, {k: v[: v.shape[0] // 2]
                                    for k, v in batch.items()})
        else:
            raise ValueError(fault)
        return broken
    return factory


def plant(monkeypatch, fault: str) -> None:
    """Break the timed path: the train step returns its state unchanged,
    or drops half of the batch and takes the mean over the rest; or the
    save path alters one byte of the parameters it hands to the writer."""
    import repro.train.train_step as train_step
    import repro.train.trainer as trainer
    from repro.checkpoint import sharded

    if fault in ("unchanged", "half_batch"):
        real = train_step.make_train_step
        monkeypatch.setattr(trainer, "make_train_step",
                            _step_factory(real, fault))
        monkeypatch.setattr(train_step, "make_train_step",
                            _step_factory(real, fault))
    elif fault == "altered_save":
        real = sharded.extract_snapshot

        def altered(*args, **kwargs):
            snap = real(*args, **kwargs)
            name = next(n for n in snap.leaves if n.startswith("params/"))
            index, arr = snap.leaves[name].pieces[0]
            arr = np.array(arr, copy=True)
            arr.view(np.uint16).reshape(-1)[0] ^= 0x4000
            snap.leaves[name].pieces[0] = (index, arr)
            return snap
        monkeypatch.setattr(sharded, "extract_snapshot", altered)
    elif fault != "none":
        raise ValueError(fault)
