"""Compile the main kernel path for the chip, without the chip.

The TPU's compiler is installed beside the CPU backend and compiles for a
chip that is described, not attached (the `on-chip-measurement` guide,
section 2.3). Interpret mode cannot show what it refuses — an operand
Mosaic cannot lay out, a slice off the tiling, more VMEM than a kernel may
use, a step that does not fit 16 GB — so each Pallas entry of the main
path is compiled here with `interpret=False` at the two widths users run
(`base`: C=512/G=512/H=8, the paper's: C=128/G=512/H=4; L=512, bf16), plus
the whole `base` train step. Nothing runs: results and times come only
from `chip_smoke.py` on the chip.

Skipped where the v5e topology cannot be described (no TPU compiler in the
installation).
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp
# The compiler library guards a real chip with a lock file; nothing here
# touches a chip, and under pytest-xdist several workers describe the
# topology at once.
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax
import jax.numpy as jnp
import pytest

from proteinbert_tpu import kernels as K
from proteinbert_tpu.configs import ModelConfig, get_preset
from proteinbert_tpu.kernels import attention, one_pass
from proteinbert_tpu.models import proteinbert

V5E_HBM_BYTES = 16 * 1024 ** 3

B, L, S = 8, 512, 8
WIDTHS = {  # name: (C, G, H, key_dim)
    "base": (512, 512, 8, 64),
    "paper": (128, 512, 4, 64),
}


@pytest.fixture(scope="module")
def topo():
    """A described v5e host (2x2) to compile for. The persistent cache is
    off around these compiles: an entry written for a described device
    cannot be read back without the device, and the next compile would
    warn."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any refusal means no compiler
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(topo):
    """One chip of it."""
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _on(chip, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree)


def _block_shapes(width, quantized=False):
    C, G, H, kd = WIDTHS[width]
    cfg = ModelConfig(local_dim=C, global_dim=G, num_heads=H, key_dim=kd,
                      num_blocks=1, dtype="bfloat16")
    blk = jax.eval_shape(lambda k: proteinbert.block_init(k, cfg),
                         jax.random.PRNGKey(0))
    if quantized:
        from proteinbert_tpu.parallel.quant import quantize_params

        blk = jax.eval_shape(quantize_params, blk)
    track = {k: blk[k] for k in ("narrow_conv", "wide_conv", "local_ln1",
                                 "local_dense", "local_ln2")}
    return C, G, track, blk["attention"]


def _sds(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


def _entry(name, width):
    """(function, abstract args) of one kernel entry at one width."""
    quantized = name.endswith("_int8")
    C, G, track, attn = _block_shapes(width, quantized)
    x, bc, bcs = _sds((B, L, C)), _sds((B, C)), _sds((B, S, C))
    g, gs = _sds((B, G)), _sds((B, S, G))
    seg, pad = _sds((B, L), jnp.int32), _sds((B, L), jnp.bool_)
    if name == "fused_local_track":  # forward AND the custom-VJP backward
        def f(p, x, b):
            return jax.value_and_grad(
                lambda p, x: K.fused_local_track(p, x, b, 1, 5, False)
                .astype(jnp.float32).sum(), argnums=(0, 1))(p, x)
        return f, (track, x, bc)
    if name == "fused_local_track_segments":
        return (lambda p, x, b, s: K.fused_local_track_segments(
            p, x, b, s, 1, 5, False)), (track, x, bcs, seg)
    if name == "fused_global_attention":
        return (lambda p, x, g, m: attention.fused_global_attention(
            p, x, g, m, interpret=False)), (attn, x, g, pad)
    if name == "fused_packed_attention":
        return (lambda p, x, g, s, m: attention.fused_packed_attention(
            p, x, g, s, m, interpret=False)), (attn, x, gs, seg, pad)
    if name.startswith("fused_onepass_dense"):
        return (lambda tp, ap, x, b, g, m: one_pass.fused_onepass_dense(
            tp, ap, x, b, g, m, 1, 5, interpret=False)), (
                track, attn, x, bc, g, pad)
    if name.startswith("fused_onepass_segments"):
        return (lambda tp, ap, x, b, g, s, m: one_pass.fused_onepass_segments(
            tp, ap, x, b, g, s, m, 1, 5, interpret=False)), (
                track, attn, x, bcs, gs, seg, pad)
    raise KeyError(name)


# The six entries of the main kernel path. The one-pass pair compiles in
# both arms: fp32-held weights, and int8 leaves dequantized in the kernel.
ENTRIES = ("fused_local_track", "fused_local_track_segments",
           "fused_global_attention", "fused_packed_attention",
           "fused_onepass_dense", "fused_onepass_segments")
CASES = ([(e, w) for w in WIDTHS for e in ENTRIES]
         + [(e + "_int8", w) for w in WIDTHS
            for e in ("fused_onepass_dense", "fused_onepass_segments")])


@pytest.mark.parametrize("entry,width", CASES,
                         ids=[f"{e}-{w}" for e, w in CASES])
def test_kernel_entry_compiles_for_v5e(chip, entry, width):
    """`interpret=False` → a Mosaic kernel in the compiled program, or the
    compiler's own refusal as the failure. At `base` width the one-pass
    gate defers (its VMEM pricing) and the two-kernel composition is what
    compiles — two custom calls; at the paper width the one-pass program
    itself — one."""
    fn, args = _entry(entry, width)
    text = jax.jit(fn).lower(*_on(chip, args)).compile().as_text()
    calls = text.count("tpu_custom_call")
    if entry.startswith("fused_onepass"):
        assert calls == (2 if width == "base" else 1), calls
    else:
        assert calls >= 1, "no tpu_custom_call in the compiled text"
    # Each pallas_call's `name=` is the compiled instruction's name (what
    # a device trace's operations carry), so a cell with `use_pallas`
    # finds its kernels in `device_ops` by name.
    for name in _kernel_names(entry, width):
        assert f"%{name}." in text or f"{name})/pallas_call" in text, name


def _kernel_names(entry, width):
    local = ("pbt_local_track_segments" if "segments" in entry
             or "packed" in entry else "pbt_local_track")
    if entry.startswith("fused_onepass"):
        return ((local, "pbt_global_attention") if width == "base"
                else ("pbt_onepass",))
    return ("pbt_global_attention",) if "attention" in entry else (local,)


@pytest.mark.parametrize("entry,name", [
    ("fused_local_track", "pbt_local_track_tiled"),
    ("fused_local_track_segments", "pbt_local_track_segments_tiled")])
def test_channel_tiled_kernels_are_named(entry, name, monkeypatch):
    """Above MAX_PALLAS_DIM the local track runs the channel-tiled
    kernels, which no cell above compiles: their names are read from the
    jaxpr (no chip, nothing compiled)."""
    monkeypatch.setitem(WIDTHS, "wide", (1024, 1024, 16, 64))
    fn, args = _entry(entry, "wide")
    assert f"name={name}" in str(jax.make_jaxpr(fn)(*args))


def test_base_train_step_fits_the_chip(chip):
    """The whole `base` step at the preset's own batch (128 x 512, bf16,
    remat "convs") compiles for one v5e and its arguments, outputs and
    temporaries fit the 16 GB of HBM with the donated state aliased."""
    from proteinbert_tpu.train import create_train_state, train_step

    cfg = get_preset("base")
    state = jax.eval_shape(
        lambda: create_train_state(jax.random.PRNGKey(0), cfg))
    batch = {
        "tokens": _sds((cfg.data.batch_size, cfg.data.seq_len), jnp.int32),
        "annotations": _sds((cfg.data.batch_size,
                             cfg.model.num_annotations), jnp.float32),
    }
    compiled = train_step.lower(
        _on(chip, state), _on(chip, batch), cfg).compile()
    m = compiled.memory_analysis()
    need = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert 0 < need < V5E_HBM_BYTES, (need, m)
    # Donation took: the new state lives in the old one's buffers.
    assert m.alias_size_in_bytes > 0.9 * m.output_size_in_bytes, m


@pytest.mark.parametrize("rows", [64, 512])
def test_served_packed_encode_runs_the_segment_kernel_on_v5e(
        chip, rows, monkeypatch):
    """The serving cell's executable (`_packed_encode_batch` of `base`:
    rows x 1024, eight segments a row) compiles for one v5e with its local
    track on the segment kernel (ISSUE 42): one Mosaic call in the scanned
    block body, named inside the `local_track` scope so that the scope
    metrics go on reading it, counted `pallas/packed` once, and the
    512-row class's temporaries far under the 6.5 GB XLA's nine-tap
    lowering took. The backend predicate is steered here, in the test: the
    sandbox's backend is the CPU."""
    from proteinbert_tpu import inference
    from proteinbert_tpu.kernels import fused_block

    monkeypatch.setattr(fused_block, "pallas_compiles", lambda: True)
    cfg = get_preset("base").model
    params = jax.eval_shape(lambda k: proteinbert.init(k, cfg),
                            jax.random.PRNGKey(0))
    before = dict(fused_block.PATH_TOTAL)
    compiled = inference._packed_encode_batch.lower(
        _on(chip, params), *_on(chip, (
            _sds((rows, 1024), jnp.int32), _sds((rows, 1024), jnp.int32),
            _sds((rows, S, cfg.num_annotations), jnp.float32))),
        cfg).compile()
    moved = {k: v - before.get(k, 0)
             for k, v in fused_block.PATH_TOTAL.items()
             if v != before.get(k, 0)}
    assert moved == {("pallas", "packed"): 1}, moved
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1, text.count("tpu_custom_call")
    assert "local_track/pbt_local_track_segments/pallas_call" in text
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < 3 * 1024 ** 3, m


def test_ragged_mesh_executable_runs_the_kernel_on_each_chip(
        topo, monkeypatch):
    """`--serve-mode ragged --mesh` on a v5e host: the dispatcher's packed
    executable (`on_each_replica` of `_packed_encode_batch`, 256 rows over
    data x fsdp = 2 x 2) compiles for the four chips. Handed to the
    partitioner the entry does not lower at all ("Mosaic kernels cannot be
    automatically partitioned"); stated as a map over the mesh, each chip
    runs the kernel on ITS 64 rows and nothing crosses chips: no
    all-gather of the activations, no collective of any kind."""
    import re

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from proteinbert_tpu import inference
    from proteinbert_tpu.kernels import fused_block
    from proteinbert_tpu.parallel.sharding import (
        on_each_replica, serve_batch_sharding,
    )

    monkeypatch.setattr(fused_block, "pallas_compiles", lambda: True)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2, 1, 1),
                ("data", "fsdp", "model", "seq"))
    cfg = get_preset("base").model
    rows = 256
    params = jax.eval_shape(lambda k: proteinbert.init(k, cfg),
                            jax.random.PRNGKey(0))
    placed = serve_batch_sharding(mesh)
    args = (
        jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, P())), params),
        jax.ShapeDtypeStruct((rows, 1024), jnp.int32,
                             sharding=placed["tokens"]),
        jax.ShapeDtypeStruct((rows, 1024), jnp.int32,
                             sharding=placed["segment_ids"]),
        jax.ShapeDtypeStruct((rows, S, cfg.num_annotations), jnp.float32,
                             sharding=placed["annotations"]))
    with pytest.raises(NotImplementedError, match="automatically partition"):
        inference._packed_encode_batch.lower(*args, cfg)
    text = on_each_replica(inference._packed_encode_batch, mesh).lower(
        *args, cfg).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert re.search(  # the kernel's own output: a chip's quarter
        rf"pbt_local_track_segments\S* = bf16\[{rows // 4},1024,512\]", text)
    assert not re.search(
        r"\b(all-gather|all-reduce|all-to-all|collective-permute|"
        r"reduce-scatter)(-start)?\(", text)


def test_decoder_expert_layer_compiles_for_v5e(chip):
    """One expert layer of the causal decoder at GLM-4.7-Flash's
    published widths (models/glm_moe.py: latent attention through the
    Pallas flash kernel, the router over 64, grouped products over the 8
    experts held, the shared expert), forward and backward, one row of
    8,192 tokens: the flash kernels are in the compiled program (forward,
    and the two of the backward pass), and so is the grouped products'
    loop with its data-dependent trip count."""
    import dataclasses

    from proteinbert_tpu.models import glm_moe

    cfg = dataclasses.replace(get_preset("glm47flash_ep8").model,
                              num_hidden_layers=2, num_nextn_predict_layers=0)
    shapes = glm_moe.param_shapes(cfg)["layers"]
    layer = jax.tree.map(lambda s: _sds(s[1:], jnp.float32), shapes,
                         is_leaf=lambda s: isinstance(s, tuple))
    x = _sds((1, 8192, cfg.hidden_size))
    seg = _sds((1, 8192), jnp.int32)
    bias = _sds((cfg.n_routed_experts,), jnp.float32)

    def f(p, bias, x, seg):
        def loss(p, x):
            y, stats = glm_moe.expert_layer(
                p, bias, x, seg, jnp.zeros_like(seg), cfg)
            return y.astype(jnp.float32).sum(), stats["dropped"]
        return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(p, x)

    text = jax.jit(f).lower(*_on(chip, (layer, bias, x, seg))).compile().as_text()
    assert text.count("tpu_custom_call") >= 3, "flash forward + two backward kernels"
    assert "while(" in text or " while" in text


def test_kda_core_compiles_for_v5e(chip):
    """The KDA core at Ling-3.0-flash's published sizes (2 rows x 8,192,
    32 heads of 128, chunks of 64): the two Pallas kernels
    (kernels/kda.py: A and B; the walk of the state, float32, the state
    in VMEM) and the triangular inverse between them in XLA compile for
    one v5e."""
    from functools import partial

    from proteinbert_tpu.ops import kda

    shape = (2, 8192, 32, 128)
    args = (_sds(shape), _sds(shape), _sds(shape), _sds(shape, jnp.float32),
            _sds(shape[:3], jnp.float32), _sds(shape[:2], jnp.int32))
    text = jax.jit(partial(kda._kda_tpu, chunk=64)).lower(
        *_on(chip, args)).compile().as_text()
    assert text.count("tpu_custom_call") == 2, "A and B; the state walk"


def test_ssd_core_compiles_for_v5e(chip):
    """The Mamba-2 recurrence at Nemotron-3-Super's published sizes (one
    row of 8,192, 128 heads of 64 in 8 groups on a state of 128, chunks
    of 128, bfloat16 operands): ONE Pallas kernel (kernels/ssd.py: a
    group's state in VMEM for the whole row) compiles for one v5e, and no
    operand or result of x's size is copied or transposed around it."""
    from proteinbert_tpu.ops import ssd

    B, L, H, P, G, N = 1, 8192, 128, 64, 8, 128
    assert ssd.kernel_takes(_sds((B, L, H, P)), _sds((B, L, G, N)), 128, jnp.bfloat16)

    def flat(x, dt, a, b, c, seg):      # as `mamba_mixer` holds them
        return ssd._ssd_tpu(x.reshape(B, L, H, P), dt, a, b.reshape(B, L, G, N),
                            c.reshape(B, L, G, N), seg, 128,
                            jnp.bfloat16).reshape(B, L, H * P)

    args = (_sds((B, L, H * P)), _sds((B, L, H), jnp.float32), _sds((H,), jnp.float32),
            _sds((B, L, G * N)), _sds((B, L, G * N)), _sds((B, L), jnp.int32))
    text = jax.jit(flat).lower(*_on(chip, args)).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert "bf16[1,8192,128,64]" not in text and "f32[1,8192,128,64]" not in text


@pytest.mark.parametrize("width,block", [(2560, 384), (2048, 512)])
def test_the_experts_row_movers_compile_for_v5e(chip, width, block):
    """The experts' loop's two movers (kernels/moe_rows.py) at the served
    decoder's and the trained decoder's sizes, 16,384 tokens: a row of
    2,560 is a slab of 24 sublanes (Mosaic refuses one of 20), and the
    sum is updated in place."""
    from proteinbert_tpu.kernels import moe_rows

    rows = 16384 + block
    slabs = lambda dt, n=rows: jax.eval_shape(  # noqa: E731
        moe_rows.pack, _sds((n, width), dt))
    tok, n = _sds((block,), jnp.int32), _sds((), jnp.int32)
    for dt in (jnp.bfloat16, jnp.float32):
        text = jax.jit(moe_rows.gather_rows).lower(
            *_on(chip, (slabs(dt), tok, n))).compile().as_text()
        assert text.count("tpu_custom_call") == 1
    compiled = jax.jit(moe_rows.scatter_add_rows, donate_argnums=0).lower(
        *_on(chip, (slabs(jnp.float32), slabs(jnp.float32, block),
                    tok, n))).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= rows * width * 4, m


def test_served_hybrid_decoder_fits_the_chip(chip):
    """The packed executable `serve-ling3flash-sat` times (the whole
    served share of Ling-3.0-flash: 5.07 B bfloat16 parameters, 2 rows x
    8,192 x 16 documents) compiles for one v5e and fits it: the two
    kernels' calls are there (the KDA core's two in each of the three
    KDA scans, the flash core), and arguments + temporaries stay
    under the 15.75 GiB the compiler holds a program to."""
    from proteinbert_tpu import inference
    from proteinbert_tpu.models import glm_moe

    cfg = get_preset("ling3flash_ep4").model
    params = glm_moe.served_abstract(cfg)
    assert glm_moe.served_param_count(cfg) == 5_068_766_144
    grid = _sds((2, 8192), jnp.int32)
    compiled = inference._packed_decoder_embed_batch.lower(
        *_on(chip, (params, grid, grid, _sds((2, 16, 0), jnp.float32))),
        cfg=cfg).compile()
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes > 9.4 * 2 ** 30
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 15.75 * 2 ** 30, m
    assert compiled.as_text().count("tpu_custom_call") >= 7


def test_served_cca_decoder_fits_the_chip(chip):
    """The packed executable `serve-zaya1-8b-sat` times (ZAYA1-8B's
    published layers 0-23 whole: 5.52 B bfloat16 parameters, 2 rows x
    8,192 x 16 documents) compiles for one v5e beside Ling's and fits
    it: the flash forward kernel with grouped keys (8 query heads on 2
    key heads of 128, K and V never repeated) and the experts' two row
    movers are there, and arguments + temporaries stay under the
    15.75 GiB the compiler holds a program to."""
    from proteinbert_tpu import inference
    from proteinbert_tpu.models import glm_moe

    cfg = get_preset("zaya1_8b_pp2").model
    params = glm_moe.served_abstract(cfg)
    assert glm_moe.served_param_count(cfg) == 5_519_138_864
    grid = _sds((2, 8192), jnp.int32)
    compiled = inference._packed_decoder_embed_batch.lower(
        *_on(chip, (params, grid, grid, _sds((2, 16, 0), jnp.float32))),
        cfg=cfg).compile()
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes > 10.2 * 2 ** 30
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 15.75 * 2 ** 30, m
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3
    assert "segment_flash_fwd" in text
    # no copy of K or V at the eight query heads: the kernel's key
    # operand is (rows, 2 key heads, positions, 128)
    assert "bf16[2,2,8192,128]" in text and "bf16[2,8,8192,128]" in text


def test_served_pattern_decoder_fits_the_chip(chip):
    """The packed executable `serve-nemotron3super-sat` times (one chip's
    share of Nemotron-3-Super's first pipeline stage: 5.38 B bfloat16
    parameters, 2 rows x 8,192 x 16 documents) compiles for one v5e
    beside Ling's and ZAYA1's and fits it: the flash forward kernel at a
    group of 16 (32 query heads on 2 key heads of 128, K and V never
    repeated) and the experts' two row movers over slabs of the
    1,024-wide latent are there, the state-space recurrence is its own
    kernel (`ssd_chunks`, once a traced mixer) with x handed to it as the
    split leaves it, and arguments + temporaries stay under the 15.75 GiB
    the compiler holds a program to."""
    from proteinbert_tpu import inference
    from proteinbert_tpu.models import glm_moe

    cfg = get_preset("nemotron3super_ep4").model
    params = glm_moe.served_abstract(cfg)
    assert glm_moe.served_param_count(cfg) == 5_382_756_608
    grid = _sds((2, 8192), jnp.int32)
    compiled = inference._packed_decoder_embed_batch.lower(
        *_on(chip, (params, grid, grid, _sds((2, 16, 0), jnp.float32))),
        cfg=cfg).compile()
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes > 10.0 * 2 ** 30
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 15.75 * 2 ** 30, m
    text = compiled.as_text()
    # `MEMEMEM*EMEME`: the flash core once; the movers in (ME) x 3, (EM) x 2
    # and the last E, each traced once
    assert text.count("segment_flash_fwd") >= 1
    assert text.count("moe_gather_rows") >= 3 and text.count("moe_scatter_add_rows") >= 3
    # (ME) x 3, M, (EM) x 2: three traced mixers, no scan over chunks left
    assert text.count("ssd_chunks") >= 3
    assert "bf16[2,8192,128,64]" not in text and "f32[2,8192,128,64]" not in text
    assert "bf16[2,2,8192,128]" in text and "bf16[2,32,8192,128]" in text
