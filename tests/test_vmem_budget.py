"""Pins the VMEM-guard decisions across the shared pricing refactor
(kernels/vmem_budget.py, ISSUE 16).

The three historical guards (`pallas_supported`,
`pallas_segments_supported`, `pallas_attention_supported`) and the
one-pass guard (`pallas_onepass_supported`) now compose the same
primitive formulas. These tests hardcode the decisions the guards made
BEFORE the extraction on a representative shape grid — supported and
unsupported points on every rejection axis (lane alignment, tiled
ceiling, short rows, segment count, budget overflow) — so any change
to the shared arithmetic that silently flips a dispatch decision
fails here, not in a production fallback.
"""

import pytest

from proteinbert_tpu.kernels import attention as ka
from proteinbert_tpu.kernels import fused_block as fb
from proteinbert_tpu.kernels import one_pass as op
from proteinbert_tpu.kernels import vmem_budget as vb

# (local_dim, seq_len, dtype) -> decision, pinned pre-refactor.
DENSE_GRID = [
    ((128, 128, "float32"), True),
    ((128, 512, "bfloat16"), True),
    ((512, 512, "bfloat16"), True),
    ((512, 1024, "float32"), False),
    ((1024, 512, "bfloat16"), True),
    ((2048, 512, "bfloat16"), False),
    ((192, 128, "bfloat16"), False),   # not lane-aligned
    ((130, 128, "bfloat16"), False),   # not lane-aligned
    ((128, 4, "float32"), False),      # sublane-short row
    ((4096, 512, "bfloat16"), False),  # beyond the tiled ceiling
]

# (local_dim, seq_len, max_segments, dtype) -> decision.
SEGMENT_GRID = [
    ((128, 128, 4, "float32"), True),
    ((128, 512, 8, "bfloat16"), True),
    ((512, 512, 8, "bfloat16"), True),
    ((512, 1024, 8, "float32"), False),
    ((1024, 128, 2, "bfloat16"), True),
    ((1024, 512, 64, "bfloat16"), True),
    ((2048, 512, 8, "bfloat16"), False),
    ((128, 128, 0, "float32"), False),  # no segments
    ((192, 128, 4, "bfloat16"), False),
    ((128, 4, 4, "float32"), False),
    # ISSUE 42: the L tile comes from the budget (`_segment_tile`), so the
    # serving row has a plan (256 rows a tile) and a longer one too (128);
    # a row that passes the budget by itself still has none.
    ((512, 1024, 1, "bfloat16"), True),
    ((512, 1024, 8, "bfloat16"), True),
    ((512, 1024, 16, "bfloat16"), True),
    ((512, 2048, 8, "bfloat16"), True),
    ((512, 4096, 8, "bfloat16"), False),
]

# (local_dim, global_dim, seq_len, max_segments, key_dim, num_heads,
#  dtype) -> decision.
ATTENTION_GRID = [
    ((128, 64, 128, 4, 16, 4, "float32"), True),
    ((128, 64, 128, 1, 16, 4, "float32"), True),
    ((512, 512, 512, 8, 64, 8, "bfloat16"), True),
    ((1024, 512, 512, 8, 64, 8, "bfloat16"), True),
    ((1024, 512, 2048, 64, 64, 8, "bfloat16"), False),
    ((2048, 512, 2048, 64, 64, 8, "float32"), False),
    ((128, 60, 128, 4, 16, 4, "float32"), True),
    ((130, 64, 128, 4, 16, 4, "float32"), False),
    ((128, 64, 4, 4, 16, 4, "float32"), False),
    ((2048, 512, 2048, 200, 64, 8, "float32"), False),
]


@pytest.mark.parametrize("shape,want", DENSE_GRID)
def test_dense_guard_pinned(shape, want):
    C, L, dt = shape
    assert fb.pallas_supported(C, L, dt) is want


@pytest.mark.parametrize("shape,want", SEGMENT_GRID)
def test_segment_guard_pinned(shape, want):
    C, L, S, dt = shape
    assert fb.pallas_segments_supported(C, L, S, dt) is want


@pytest.mark.parametrize("shape,want", ATTENTION_GRID)
def test_attention_guard_pinned(shape, want):
    C, G, L, S, k, H, dt = shape
    assert ka.pallas_attention_supported(C, G, L, S, k, H, dt) is want


def test_lane_roundup_is_a_roundup():
    assert vb.lanes(1) == 128
    assert vb.lanes(128) == 128
    assert vb.lanes(129) == 256
    assert vb.lanes(192) == 256


def test_constants_reexported_under_historical_names():
    """attention.py/fused_block.py consumers keep the names they
    imported before the extraction."""
    assert fb.MAX_PALLAS_DIM == vb.MAX_PALLAS_DIM == 512
    assert fb.MAX_TILED_DIM == vb.MAX_TILED_DIM == 2048
    assert fb._LANE == vb.LANE == 128
    assert fb._VMEM_BUDGET == vb.VMEM_BUDGET == 13 * 1024 * 1024


def test_onepass_guard_composes_shared_pricing():
    """The one-pass guard prices the UNION working set: shapes whose
    two-kernel halves both fit can still overflow the fused budget
    (honest fallback), and every structural rejection axis matches the
    shared prechecks."""
    # The smoke/test shape fits.
    assert op.pallas_onepass_supported(128, 64, 128, 4, 16, 4,
                                       "float32")
    assert op.pallas_onepass_supported(128, 64, 128, 1, 16, 4,
                                       "float32")
    # Structural rejections mirror the other families.
    assert not op.pallas_onepass_supported(130, 64, 128, 4, 16, 4,
                                           "float32")
    assert not op.pallas_onepass_supported(128, 64, 4, 4, 16, 4,
                                           "float32")
    assert not op.pallas_onepass_supported(128, 64, 128, 0, 16, 4,
                                           "float32")
    assert not op.pallas_onepass_supported(128, 60, 128, 4, 16, 4,
                                           "float32")
    # One-pass has NO channel-tiled variant: beyond MAX_PALLAS_DIM it
    # must defer to the two-kernel composition even though both halves
    # individually support C=1024.
    assert fb.pallas_segments_supported(1024, 128, 2, "bfloat16")
    assert ka.pallas_attention_supported(1024, 512, 128, 2, 64, 8,
                                         "bfloat16")
    assert not op.pallas_onepass_supported(1024, 512, 128, 2, 64, 8,
                                           "bfloat16")
    # Budget overflow inside the supported structural range: fp32
    # C=512 weights alone exceed the shared budget.
    assert not op.pallas_onepass_supported(512, 512, 512, 8, 64, 8,
                                           "float32")
