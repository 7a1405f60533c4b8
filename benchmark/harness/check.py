"""The comparison that decides ``correct`` for a training cell: what the
timed path produced in its first steps against the plain reference's.

Each number has a limit of its own in the cell's file (``limits``):

* ``loss``: per step, |program - reference| / |reference|;
* ``grad_norm_worst``: the first gradient as the optimiser got it, by the
  worst leaf: the gap between the program's norm of a leaf and the
  reference's (not the norm of their difference), against the reference's
  norm of that leaf or of the median leaf, whichever is larger (some
  gradients are all but zero). A widest gap: it swings from seed to seed;
* ``grad_norm_median``: the median over the leaves of that same gap, which
  is steady from seed to seed and is what tells a lower precision apart;
* ``delta_norm_worst``: the parameters' change after the last step, by the
  worst leaf, likewise (there to catch a step that leaves its state as it
  was: every gap is then 1).

Every number is printed beside its limit, in every run."""

from __future__ import annotations

import math
import statistics
from typing import Dict, List, Tuple


def leaf_norms(tree: Dict[str, "jax.Array"]) -> Dict[str, float]:
    """Norm of every leaf, in one jitted call."""
    import jax
    import jax.numpy as jnp
    out = jax.jit(lambda t: {k: jnp.linalg.norm(a.astype(jnp.float32))
                             for k, a in t.items()})(tree)
    return {k: float(v) for k, v in out.items()}


def leaf_delta_norms(now: dict, before: dict) -> Dict[str, float]:
    """Norm of every leaf's change. ``before`` is brought to ``now``'s
    placement first (across chips the parameters are replicated)."""
    import jax
    import jax.numpy as jnp
    before = {k: jax.device_put(a, now[k].sharding) for k, a in before.items()}
    out = jax.jit(lambda a, b: {k: jnp.linalg.norm(a[k] - b[k])
                                for k in a})(now, before)
    return {k: float(v) for k, v in out.items()}


def leaf_gaps(program: Dict[str, float],
              reference: Dict[str, float]) -> Dict[str, float]:
    if set(program) != set(reference):
        odd = sorted(set(program) ^ set(reference))[:6]
        raise ValueError(f"program and reference differ in leaves: {odd}")
    floor = statistics.median(reference.values())
    out = {}
    for name, ref in reference.items():
        gap = abs(program[name] - ref) / max(ref, floor, 1e-30)
        out[name] = gap if math.isfinite(gap) else math.inf
    return out


def worst_leaf_gap(program: Dict[str, float],
                   reference: Dict[str, float]) -> Tuple[float, str]:
    gaps = leaf_gaps(program, reference)
    where = max(gaps, key=gaps.get)
    return gaps[where], where


def median_leaf_gap(program: Dict[str, float],
                    reference: Dict[str, float]) -> float:
    return statistics.median(leaf_gaps(program, reference).values())


def compare_training(program: dict, reference: dict, limits: dict,
                     say=print) -> Tuple[bool, List[dict]]:
    """``program`` and ``reference`` hold ``losses`` (a list),
    ``grad_norms`` and ``delta_norms`` (leaf -> norm). Returns whether every
    number is within its limit, and the numbers."""
    rows = []
    for i, (p, r) in enumerate(zip(program["losses"], reference["losses"])):
        gap = abs(p - r) / abs(r) if math.isfinite(p) and r else math.inf
        rows.append({"what": f"loss.step{i + 1}", "value": gap,
                     "limit": limits["loss"][i], "program": p,
                     "reference": r})
    if len(program["losses"]) != len(reference["losses"]):
        rows.append({"what": "loss.steps", "value": math.inf, "limit": 0.0})
    for key in ("grad_norm", "delta_norm"):
        gap, leaf = worst_leaf_gap(program[key + "s"], reference[key + "s"])
        rows.append({"what": f"{key}.worst_leaf", "value": gap,
                     "limit": limits[key + "_worst"], "leaf": leaf})
        if key == "grad_norm":
            rows.append({"what": "grad_norm.median_leaf",
                         "value": median_leaf_gap(program["grad_norms"],
                                                  reference["grad_norms"]),
                         "limit": limits["grad_norm_median"]})
    ok = True
    for row in rows:
        row["ok"] = bool(row["value"] <= row["limit"])
        ok = ok and row["ok"]
        say("check " + " ".join(f"{k}={v}" for k, v in row.items()))
    return ok, rows
