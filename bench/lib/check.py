"""What decides ``correct``: the program's simulated state and statistics
against the plain reference, leaf by leaf and exactly.

Both sides are brought to one canonical form first: every circular queue
rotated to head 0, every dead queue or FIFO slot zeroed, and the scratch
registers that keep their last value after going idle (the memory server's
response template, the write serializer, NI destinations with nothing
outstanding) zeroed. Two simulations that agree on every live bit then
compare equal; one beat that differs does not.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

STATE_GROUPS = ("fabric", "eps")


def flat_state(st) -> dict:
    """The program's ``SimState`` as ``{"fabric.in_buf": array, ...}``."""
    out = {}
    for group in STATE_GROUPS:
        sub = getattr(st, group)
        for f in dataclasses.fields(sub):
            v = getattr(sub, f.name)
            if v is not None:
                out[f"{group}.{f.name}"] = np.asarray(v)
    out["cycle"] = np.asarray(st.cycle)
    return out


def with_leaves(st, leaves: dict):
    """The program's ``SimState`` with the named leaves (``"eps.n_seq"``)
    replaced by the given arrays."""
    groups = {}
    for name, v in leaves.items():
        group, leaf = name.split(".")
        groups.setdefault(group, {})[leaf] = jnp.asarray(v)
    return dataclasses.replace(st, **{
        g: dataclasses.replace(getattr(st, g), **fs) for g, fs in groups.items()})


def _live(cnt, depth):
    return np.arange(depth) < np.asarray(cnt)[..., None]


def canonical(state: dict) -> dict:
    """The canonical form of a flat state (see the module docstring)."""
    s = dict(state)
    for side in ("in", "out"):
        buf, cnt = s[f"fabric.{side}_buf"], s[f"fabric.{side}_cnt"]
        s[f"fabric.{side}_buf"] = np.where(_live(cnt, buf.shape[-2])[..., None], buf, 0)
    mq, head, cnt = s["eps.mq"], s["eps.mq_head"], s["eps.mq_cnt"]
    Q = mq.shape[1]
    rot = (head[:, None] + np.arange(Q)) % Q
    mq = np.take_along_axis(mq, rot[..., None], axis=1)
    s["eps.mq"] = np.where(_live(cnt, Q)[..., None], mq, 0)
    s["eps.mq_head"] = np.zeros_like(head)
    eg, ready, head, cnt = s["eps.eg"], s["eps.eg_ready"], s["eps.eg_head"], s["eps.eg_cnt"]
    Q = ready.shape[-1]
    rot = (head[..., None] + np.arange(Q)) % Q
    live = _live(cnt, Q)
    s["eps.eg"] = np.where(live[..., None], np.take_along_axis(eg, rot[..., None], axis=2), 0)
    s["eps.eg_ready"] = np.where(live, np.take_along_axis(ready, rot, axis=2), 0)
    s["eps.eg_head"] = np.zeros_like(head)
    s["eps.m_flit"] = np.where(s["eps.m_active"][:, None], s["eps.m_flit"], 0)
    idle = s["eps.w_stream"] < 0
    for k in ("w_left", "w_beats", "w_dst", "w_txn", "w_ts"):
        s[f"eps.{k}"] = np.where(idle, 0, s[f"eps.{k}"])
    s["eps.ni_dst"] = np.where(s["eps.ni_cnt"] == 0, -1, s["eps.ni_dst"])
    return s


def state_mismatch(program: dict, reference: dict) -> int:
    """Elements of the reference's leaves that the program's canonical
    state gets wrong; a leaf missing or of another shape counts whole."""
    p, r = canonical(program), canonical(reference)
    bad = 0
    for k, ref in r.items():
        got = p.get(k)
        if got is None or np.shape(got) != np.shape(ref) or got.dtype != ref.dtype:
            bad += int(np.size(ref))
        else:
            bad += int(np.sum(got != ref))
    return bad


def stats_mismatch(program: dict, reference: dict) -> int:
    """Statistics (by name) that differ or are missing."""
    return sum(k not in program or not np.array_equal(
        np.asarray(program[k]), np.asarray(v)) for k, v in reference.items())
