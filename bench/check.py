"""The comparison that decides `correct`, and the per-leaf arithmetic it
rests on.

A training cell's readings, from the program and from the plain reference
alike, are
  loss    [r1, r2, r3]   each checked step's loss;
  update  per leaf       the norm of the first update as the optimizer gets
                         it (its state after one step);
  change  per leaf       the norm of the parameters' change after the
                         checked steps.
Each number compared is a relative gap:
  loss.rN  |program - reference| / |reference|
  update, change
           the worst leaf's |norm_program - norm_reference|, over
           max(norm_reference of that leaf, the median leaf's).
Leaves whose reference update is under a thousandth of the median leaf's
are left out of both (they move by round-off alone). The limits live in
`bench/limits/<workload>.json`; only the numbers they name are compared.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np

LIMITS_DIR = Path(__file__).resolve().parent / "limits"


def leaf_layout(tree) -> tuple[list[str], list[tuple[int, int]]]:
    """Names and [start, end) slices of each leaf in the flat vector that
    `jax.tree_util` order gives (the program's flatten order)."""
    import jax
    names, spans, pos = [], [], 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        n = int(np.prod(leaf.shape)) if leaf.shape else 1
        names.append(jax.tree_util.keystr(path))
        spans.append((pos, pos + n))
        pos += n
    return names, spans


def leaf_norms_fn(spans):
    """jit-able flat vector -> float32[n_leaves] of per-leaf L2 norms."""
    import jax.numpy as jnp

    def norms(flat):
        return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
            flat[a:b].astype(jnp.float32)))) for a, b in spans])
    return norms


def gaps(prog: dict, ref: dict) -> dict:
    """Relative gaps of the program's readings against the reference's."""
    out = {}
    for i, (lp, lr) in enumerate(zip(prog["loss"], ref["loss"]), 1):
        out[f"loss.r{i}"] = abs(lp - lr) / max(abs(lr), 1e-30)
    upd_ref = np.asarray(ref["update"], np.float64)
    med = float(np.median(upd_ref))
    counted = upd_ref >= 1e-3 * med
    for key in ("update", "change"):
        p = np.asarray(prog[key], np.float64)
        r = np.asarray(ref[key], np.float64)
        scale = np.maximum(r, float(np.median(r)))
        g = np.where(counted, np.abs(p - r) / np.maximum(scale, 1e-30), 0.0)
        out[key] = float(np.max(g))
    return out


def load_limits(workload: str, directory: Path | None = None) -> dict:
    return json.loads(((directory or LIMITS_DIR) / f"{workload}.json")
                      .read_text())["limits"]


def verdict(g: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {value, limit}}): every number at or under its
    limit, and finite."""
    table = {k: {"value": g[k], "limit": limits[k]} for k in limits}
    ok = all(math.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in table.values())
    return ok, table
