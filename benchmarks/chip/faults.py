"""Faults planted under the timed path, to show that ``correct`` catches
them (``tests/test_correct.py``) and to read the numbers they give at a
cell's own size on the chip (``calibrate.py readings --fault``).

Each is a hook the drivers accept: ``service`` hooks wrap the program's
service before the gateway starts, ``step`` hooks wrap the engine's
jitted training step."""
from __future__ import annotations


def alter_answers(svc):
    """Every seventh row the device returns is off by 0.5 in its first
    head (normalized units): an answer altered where it is produced."""
    collect = svc.forward_collect

    def altered(handle):
        rows = collect(handle).copy()
        rows[::7, 0] += 0.5
        return rows
    svc.forward_collect = altered


def unchanged(step):
    """A step that returns its state unchanged (the loss still computed)."""
    def frozen(state, ids, yy):
        _, loss = step(state, ids, yy)
        return state, loss
    return frozen


def half_batch(step):
    """Half of the batch left out, the mean taken over the rest."""
    def half(state, ids, yy):
        n = ids.shape[0] // 2
        return step(state, ids[:n], yy[:n])
    return half


HOOKS = {"alter_answers": ("service", alter_answers),
         "unchanged": ("step", unchanged),
         "half_batch": ("step", half_batch)}


def hooks(name: str) -> dict:
    kind, fn = HOOKS[name]
    return {kind: fn}
