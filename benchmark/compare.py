"""The comparisons that decide `correct` (PERF.md section 2 has the limits)."""

from __future__ import annotations

import numpy as np

from benchmark import blocked


def _leaves(tree):
    import jax

    return [float(x) for x in jax.tree.leaves(tree)]


def worst_leaf_gap(program_norms, reference_norms) -> float:
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger (some leaves are all but zero)."""
    prog = np.array(_leaves(program_norms))
    ref = np.array(_leaves(reference_norms))
    scale = np.maximum(ref, np.median(ref))
    return float(np.max(np.abs(prog - ref) / scale))


def leaf_dir_gaps(program_tree, reference_tree) -> np.ndarray:
    """For every leaf, the norm of the DIFFERENCE between the program's
    and the reference's, against the reference's norm of that leaf or of
    the median leaf, whichever is larger. Where `worst_leaf_gap` sees
    only a leaf's length, this sees its direction too. The difference is
    taken in float32, squares and sums in float64 (`blocked`: block by
    block, over a few threads)."""
    import jax

    as_f32 = lambda t: [np.asarray(x, np.float32) for x in jax.tree.leaves(t)]  # noqa: E731
    program, reference = as_f32(program_tree), as_f32(reference_tree)
    diff = np.sqrt(blocked.sq_sums(program, minus=reference, diff_dtype=np.float32))
    ref = np.sqrt(blocked.sq_sums(reference))
    return diff / np.maximum(ref, np.median(ref))


def spread_of(gaps) -> list:
    """The median, the quartile, the decile and the widest of the leaves'
    gaps: printed beside the one that is compared."""
    return [float(np.quantile(gaps, q)) for q in (0.5, 0.75, 0.9, 1.0)]


def leaf_dir_spread(program_tree, reference_tree) -> list:
    return spread_of(leaf_dir_gaps(program_tree, reference_tree))


def training_checks(program: dict, reference: dict, dir_gaps=None) -> dict:
    """program / reference: {"losses", "first_grad", "first_grad_norms",
    "change_norms"}. `grad_dir_gap` is the MEDIAN leaf's gap: rounding of
    activations differs from position to position and averages out of a
    gradient summed over a batch, so the median leaf is steady from seed
    to seed; products in a lower precision move every leaf they feed.
    `dir_gaps`: `leaf_dir_gaps` of the two first gradients, where the
    caller has them already (two passes over two trees of 2.83 GB)."""
    loss_gap = max(abs(p - r) / abs(r)
                   for p, r in zip(program["losses"], reference["losses"]))
    if dir_gaps is None:
        dir_gaps = leaf_dir_gaps(program["first_grad"], reference["first_grad"])
    return {
        "loss_rel_gap": float(loss_gap),
        "grad_norm_gap": worst_leaf_gap(program["first_grad_norms"],
                                        reference["first_grad_norms"]),
        "grad_dir_gap": float(np.median(dir_gaps)),
        "change_norm_gap": worst_leaf_gap(program["change_norms"],
                                          reference["change_norms"]),
    }


def embedding_checks(served: list, reference: list) -> dict:
    """served / reference: aligned lists of {"global", "local_mean"}.
    Each answer's error is the norm of its difference from the
    reference's over the reference's norm; the root mean square over the
    sample is steady from seed to seed, the maximum is the widest gap.
    The bias is the norm of the sample's MEAN difference over the root
    mean square norm of the reference's answers: rounding of activations
    differs from request to request and averages out of it, an error in
    the weights is the same for every request and stays in."""
    out = {}
    for key in ("global", "local_mean"):
        s = np.array([np.asarray(a[key], np.float64) for a in served])
        r = np.array([np.asarray(a[key], np.float64) for a in reference])
        norms = np.linalg.norm(r, axis=1)
        errs = np.linalg.norm(s - r, axis=1) / norms
        out[f"{key}_rel_err_rms"] = float(np.sqrt(np.mean(errs ** 2)))
        out[f"{key}_rel_err_max"] = float(errs.max())
        out[f"{key}_bias"] = float(np.linalg.norm((s - r).mean(0))
                                   / np.sqrt(np.mean(norms ** 2)))
    return out
