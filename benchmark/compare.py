"""The comparisons that decide `correct` (PERF.md section 2 has the limits)."""

from __future__ import annotations

import numpy as np


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
    only a leaf's length, this sees its direction too."""
    import jax

    diff, ref = [], []
    for p, r in zip(jax.tree.leaves(program_tree),
                    jax.tree.leaves(reference_tree)):
        r = np.asarray(r, np.float32)
        d = np.asarray(p, np.float32) - r
        diff.append(np.sqrt(np.sum(np.square(d, dtype=np.float64))))
        ref.append(np.sqrt(np.sum(np.square(r, dtype=np.float64))))
    diff, ref = np.array(diff), np.array(ref)
    return diff / np.maximum(ref, np.median(ref))


def leaf_dir_spread(program_tree, reference_tree) -> list:
    """The median, the quartile, the decile and the widest of the leaves'
    gaps: printed beside the one that is compared."""
    gaps = leaf_dir_gaps(program_tree, reference_tree)
    return [float(np.quantile(gaps, q)) for q in (0.5, 0.75, 0.9, 1.0)]


def training_checks(program: dict, reference: dict) -> dict:
    """program / reference: {"losses", "first_grad", "first_grad_norms",
    "change_norms"}. `grad_dir_gap` is the MEDIAN leaf's gap: rounding of
    activations differs from position to position and averages out of a
    gradient summed over a batch, so the median leaf is steady from seed
    to seed; products in a lower precision move every leaf they feed."""
    loss_gap = max(abs(p - r) / abs(r)
                   for p, r in zip(program["losses"], reference["losses"]))
    return {
        "loss_rel_gap": float(loss_gap),
        "grad_norm_gap": worst_leaf_gap(program["first_grad_norms"],
                                        reference["first_grad_norms"]),
        "grad_dir_gap": float(np.median(leaf_dir_gaps(
            program["first_grad"], reference["first_grad"]))),
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
