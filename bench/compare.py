"""The numbers that decide ``correct``: program against plain reference.

Every number is a gap, 0 where the two agree, held to a limit from the
workload file.  Norms are compared leaf by leaf as the gap between the
program's norm and the reference's, over the reference's norm of that
leaf or of the median leaf, whichever is larger (some gradients are all
but zero), and the worst leaf counts.
"""
from __future__ import annotations

import math

import jax
import numpy as np

from bench.reference import ctgan as reference


def limited(readings: dict, limits: dict) -> dict:
    """``{name: (value, limit)}`` for every limited number; a number the
    run could not read counts as infinitely far off."""
    return {k: (float(readings.get(k, math.inf)), float(lim))
            for k, lim in limits.items()}


# -- set-up (§4.1 encoders, §4.2 weights) -----------------------------------

def setup_numbers(prog_w, ref_w, prog_enc, ref_enc, kinds, widths) -> dict:
    """``setup_weights``: the largest relative gap of the §4.2 weights;
    ``setup_cells``: the share of table cells (one per row and column)
    whose one-hot (category, or the continuous value's mode) differs;
    ``setup_alpha``: the largest gap of a continuous value's normalised
    ``alpha`` where both picked the same mode."""
    prog_w, ref_w = np.asarray(prog_w, np.float64), np.asarray(ref_w)
    out = {"setup_weights": float(np.max(np.abs(prog_w - ref_w) / ref_w))}
    cells = differ = 0
    alpha = 0.0
    for p, r in zip(prog_enc, ref_enc):
        if p.shape != r.shape:
            return dict(out, setup_cells=math.inf, setup_alpha=math.inf)
        pos = 0
        for kind, w in zip(kinds, widths):
            if kind == "continuous":
                a_p, a_r = p[:, pos], r[:, pos]
                pos += 1
            hot_p = p[:, pos:pos + w].argmax(axis=1)
            hot_r = r[:, pos:pos + w].argmax(axis=1)
            same = hot_p == hot_r
            cells += len(same)
            differ += int((~same).sum())
            if kind == "continuous" and same.any():
                alpha = max(alpha, float(np.max(np.abs(
                    a_p[same].astype(np.float64) - a_r[same]))))
            pos += w
    return dict(out, setup_cells=differ / max(cells, 1), setup_alpha=alpha)


# -- training rounds ---------------------------------------------------------

REFERENCE_FAULTS = ("half_batch", "no_merge", "weights_toward_uniform")


def _leaves(tree):
    return [np.asarray(x, np.float64)
            for x in jax.tree_util.tree_leaves(tree)]


def leaf_names(tree) -> list[str]:
    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def leaf_norms(tree) -> np.ndarray:
    return np.array([np.linalg.norm(x) for x in _leaves(tree)])


def _scaled(gaps, ref_norms) -> np.ndarray:
    """Per leaf ``gaps`` over max(the leaf's reference norm, the median
    leaf's)."""
    g, b = np.asarray(gaps, np.float64), np.asarray(ref_norms)
    den = np.maximum(b, np.median(b))
    return np.where(den > 0, g / np.where(den > 0, den, 1.0),
                    np.where(g > 0, np.inf, 0.0))


def _norm_gaps(prog, ref) -> np.ndarray:
    """Per leaf |‖prog‖ - ‖ref‖| over the larger of ‖ref‖ and the median
    leaf's ‖ref‖."""
    a = np.array([np.linalg.norm(x) for x in prog])
    b = np.array([np.linalg.norm(x) for x in ref])
    return _scaled(np.abs(a - b), b)


def _diff(a, b):
    return [x - y for x, y in zip(_leaves(a), _leaves(b))]


def train_numbers(ours: dict, ref: dict, weights,
                  nought: float = 1e-3) -> dict:
    """One checked dispatch, taken by ``ours`` (the program, or the
    reference in its place) and by the reference, both from the program's
    state at the dispatch's start.  Each side gives, one dict of G and D
    parameters per client: ``start``, ``post`` (after the first round's
    local steps), ``merge_in`` and ``merge_from`` (the last merged round's
    local results and the parameters that round started from),
    ``merged`` (that merge's result) and ``slots`` (where the dispatch
    ended); and ``losses``, the first round's ``(clients, steps, 2)``.
    The reference also gives ``mu_norms`` (per client and network, each
    leaf's norm of Adam's first moment after the first round).

    ``loss``: the first round's critic and generator losses, gap over
    max(|ref|, 1).
    ``change`` / ``change_med``: the worst and the median leaf's gap of
    the norm of the first round's parameter change, over every client.
    Leaves whose reference gradient is nought to rounding (first moment
    under ``nought`` of the median leaf's, such as a bias right before a
    batch norm) move under Adam by rounding alone and are left out.
    ``merge``: the worst leaf's distance, in any client's slot, between
    ``merged`` and the merge of ``merge_in`` with ``weights`` taken
    exactly, over the norm of that merged round's change.
    ``slots``: the worst leaf's distance between any client's slot where
    the dispatch ended and client 0's, over the norm of the dispatch's
    change: 0 where every client holds the merged model.

    Every number is one round's rounding at most: nothing read follows a
    round that the other side took from its own numbers."""
    out = {"loss": 0.0, "change": 0.0, "change_med": 0.0, "merge": 0.0,
           "slots": 0.0, "change_leaf": ""}
    pl = np.asarray(ours["losses"], np.float64)
    rl = np.asarray(ref["losses"], np.float64)
    if pl.shape != rl.shape or not np.isfinite(pl).all():
        return {k: math.inf for k in out if "leaf" not in k}
    out["loss"] = float(np.max(np.abs(pl - rl) / np.maximum(np.abs(rl), 1)))
    for net in ("g", "d"):
        names = [f"{net}{n}" for n in leaf_names(ours["start"][0][net])]
        for a, b, s, mu in zip(ours["post"], ref["post"], ours["start"],
                               ref["mu_norms"]):
            keep = mu[net] >= nought * np.median(mu[net])
            gaps = _norm_gaps(_diff(a[net], s[net]), _diff(b[net], s[net]))
            gaps = np.where(keep, gaps, 0.0)
            if gaps.max() > out["change"]:
                out["change"] = float(gaps.max())
                out["change_leaf"] = names[int(gaps.argmax())]
            out["change_med"] = max(out["change_med"],
                                    float(np.median(gaps[keep])))
        exact = reference.merge([c[net] for c in ours["merge_in"]], weights)
        scale = leaf_norms(_diff(exact, ours["merge_from"][net]))
        for c in ours["merged"]:
            far = leaf_norms(_diff(c[net], exact))
            out["merge"] = max(out["merge"], float(_scaled(far, scale).max()))
        first = ours["slots"][0][net]
        scale = leaf_norms(_diff(first, ours["start"][0][net]))
        for c in ours["slots"][1:]:
            far = leaf_norms(_diff(c[net], first))
            out["slots"] = max(out["slots"], float(_scaled(far, scale).max()))
    return out


def worst(readings: list[dict]) -> dict:
    """The largest of each number over several dispatches, with the leaf
    that gave it."""
    out = dict(readings[0])
    for r in readings[1:]:
        for k, v in r.items():
            if k.endswith("_leaf"):
                continue
            if v > out[k]:
                out[k] = v
                if f"{k}_leaf" in r:
                    out[f"{k}_leaf"] = r[f"{k}_leaf"]
    return out
