"""Ask the TPU v5e's compiler before asking the chip.

The TPU compiler is installed beside the CPU backend the tests run on,
and it compiles for a chip that is *described*, not attached. These
cases hand it the main path's kernels and whole train steps at the
flagship shapes ``chip_smoke.py`` runs — everything interpret mode on
the virtual CPU mesh cannot see: Mosaic tiling rules, kernels GSPMD
cannot partition, a program too large for the device's memory (the
compiler refuses that too). A pass means "the compiler accepts
this program"; it says nothing about results or speed (nothing runs).

Rules this file keeps (docs/TESTING.md, "Described-topology compiles"):
the topology is described inside a module-scoped fixture that skips
when it cannot be — never at import, in a ``skipif``/``parametrize``
argument or in conftest.py — because only one process may load libtpu
and every xdist worker imports every test file. Everything compiles in
the test's own process, and all such tests live in this ONE file so a
single worker owns the library.
"""

import collections
import contextlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

# Flagship shapes (chip_smoke.py).
BATCH, SEQ, HEADS, HEAD_DIM = 8, 1024, 8, 128
VOCAB, D_MODEL, LAYERS, D_FF = 8192, 1024, 8, 4096


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 - any failure means "skip"
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep it out. x64 is off
    # because the chip runs without it (conftest enables it for the
    # numpy-parity tests).
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with jax.enable_x64(False):
            yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Steer a whole train step past ``_should_interpret()``: the
    backend here is the CPU, the program being compiled is the chip's."""
    from mpi_tpu.ops import attention

    monkeypatch.setattr(attention, "_should_interpret", lambda: False)


FLASH_KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
LAYER_SCOPES = ("(attn)/", "(ffn)/", "(logits_loss)/", "/optimizer/")


def _compile(fn, *args, names=()):
    """Compile for the described chip; ``names`` are what a profiler
    trace of the program is read by (kernel names as the instructions'
    names, layer scopes inside ``op_name``) and must be in its text."""
    # A train step is jitted already, with its state donated: wrapped in
    # another jit it would lose the donation and count its state twice.
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    compiled = jitted.lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, \
        "no Mosaic kernel in the compiled program"
    missing = [n for n in names if n not in text]
    assert not missing, f"not named in the compiled program: {missing}"
    return compiled


def _qkv(sharding):
    return jax.ShapeDtypeStruct((BATCH, SEQ, HEADS, HEAD_DIM), jnp.bfloat16,
                                sharding=sharding)


def test_flash_forward_compiles(one_chip):
    from mpi_tpu.ops import flash_attention

    q = _qkv(one_chip)
    _compile(lambda q, k, v: flash_attention(q, k, v, True, None, None,
                                             False), q, q, q,
             names=["%flash_fwd"])


@pytest.mark.parametrize("shape", [
    (BATCH, SEQ, HEADS, HEADS), (2, 4096, 24, 2)],
    ids=["flagship", "starcoder2-cell"])
def test_flash_forward_backward_compiles(shape, one_chip):
    """Causal, default blocks, at the flagship's shape and at the one the
    benchmark's ``starcoder2-3b-L6.pretrain-4k-b2`` cell calls the
    kernels with (batch 2 x 4,096, 24 query heads on 2 kv heads): the
    grid in which dead, crossed and whole cells each take their own
    branch and the index maps clamp."""
    from mpi_tpu.ops import flash_attention

    def loss(q, k, v):
        out = flash_attention(q, k, v, True, None, None, False)
        return jnp.sum(out.astype(jnp.float32))

    b, s, h, hk = shape
    q = jax.ShapeDtypeStruct((b, s, h, HEAD_DIM), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, s, hk, HEAD_DIM), jnp.bfloat16,
                              sharding=one_chip)
    _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv,
             names=FLASH_KERNELS)


def _step_args(cfg, mesh, batch, seq):
    """(step, state, tokens) for ``cfg``'s train step on ``mesh``, as
    shapes carrying the shardings the program itself would commit:
    parameters to ``sane_param_specs``; the optimizer state to nothing,
    since ``init_state`` builds it with a bare ``jit(opt.init)`` and
    leaves it uncommitted; the batch to ShardedLoader's ``P('dp', None)``."""
    from mpi_tpu.models import make_train_step, sanitize_spec
    from mpi_tpu.models.transformer import sane_param_specs

    init_state, step = make_train_step(cfg, mesh=mesh)
    state = jax.eval_shape(init_state, jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda x, spec: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, spec)),
        state["params"], sane_param_specs(cfg, state["params"], mesh))
    opt = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state["opt"])
    tokens = jax.ShapeDtypeStruct(
        (batch, seq + 1), jnp.int32,
        sharding=NamedSharding(mesh, sanitize_spec(P("dp", None), mesh)))
    return step, {"params": params, "opt": opt}, tokens


def _flagship_step_args(mesh):
    from mpi_tpu.models import TransformerConfig

    cfg = TransformerConfig(
        vocab=VOCAB, d_model=D_MODEL, n_heads=HEADS, n_layers=LAYERS,
        d_ff=D_FF, max_seq=SEQ + 1, dtype=jnp.bfloat16,
        attention_impl="flash")
    return _step_args(cfg, mesh, BATCH, SEQ)


def test_flagship_train_step_compiles_one_chip(topo, compiled_kernels):
    from mpi_tpu.models import make_mesh_nd

    mesh = make_mesh_nd(1, devices=topo.devices[:1])
    _compile(*_flagship_step_args(mesh),
             names=FLASH_KERNELS + LAYER_SCOPES)


def test_flagship_train_step_compiles_four_chips(topo, compiled_kernels):
    """dp 2 x tp 2 — the mesh ``chip_smoke.py --chips 4`` trains on.
    GSPMD refuses a bare Mosaic kernel here; the flash branch's
    shard_map is what makes this compile."""
    from mpi_tpu.models import make_mesh_nd

    mesh = make_mesh_nd(4, axes=("dp", "tp"), devices=topo.devices)
    assert dict(mesh.shape) == {"dp": 2, "tp": 2}
    compiled = _compile(*_flagship_step_args(mesh),
                        names=FLASH_KERNELS + LAYER_SCOPES)
    assert "all-reduce" in compiled.as_text()  # dp grads / tp partials


EVA_KERNELS = ("%eva_remote_fwd", "%eva_remote_bwd_dq", "%eva_remote_bwd_dkv")


def test_eva_attention_compiles_at_evabyte_shapes(one_chip):
    """The EVA op, forward and backward, at the shapes of the benchmark's
    ``evabyte-L4.pretrain-16k-b1`` cell (one sequence of 16,384 bytes, 32
    heads of 128, windows of 2,048, chunks of 16): the window's keys
    through the causal flash kernels with the windows folded into the
    batch, the 1,024 summaries through the same kernels under the prefix
    mask, and the scopes a trace splits the op by."""
    from mpi_tpu.ops import eva_attention

    def loss(q, k, v, phi, mu):
        out = eva_attention(q, k, v, phi, mu, 2048, 16, interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    q = jax.ShapeDtypeStruct((1, 16384, 32, 128), jnp.bfloat16,
                             sharding=one_chip)
    vec = jax.ShapeDtypeStruct((32, 128), jnp.bfloat16, sharding=one_chip)
    _compile(jax.grad(loss, argnums=(0, 1, 2, 3, 4)), q, q, q, vec, vec,
             names=FLASH_KERNELS + EVA_KERNELS + (
                 "eva.summarize", "/eva.local/", "/eva.remote/",
                 "/eva.merge/"))


# What the compiler may count for the EvaByte cell's step: 14.723 GiB with
# what ``_REMAT_KEEPS`` holds today and its 28 dense leaves' updates each a
# pass of its own.
EVABYTE_STEP_GIB = 15.0
CHIP_GIB = 15.75

# A fusion that writes three float32 arrays of one shape: an update's new
# parameter, ``mu`` and ``nu``; ``calls=`` names its fused computation.
_STATE_TRIPLE = re.compile(
    r"= \((f32\[[\d,]*\])\{[^}]*\}, \1\{[^}]*\}, \1\{[^}]*\}\) "
    r"fusion\(.*calls=%([\w.\-]+)")


def _state_triples(text):
    """``{(shape, holds a product): fusions}`` over the compiled text's
    fusions that write a parameter and its AdamW state."""
    bodies, name = {}, None
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            name = line.split()[1 if line.startswith("ENTRY") else 0]
            bodies[name.lstrip("%")] = []
        elif name and line.startswith(" "):
            bodies[name.lstrip("%")].append(line)
    found = collections.Counter()
    for m in _STATE_TRIPLE.finditer(text):
        body = "\n".join(bodies.get(m.group(2), ()))
        found[m.group(1), "convolution(" in body] += 1
    return found


def test_evabyte_cell_train_step_fits_and_runs_forward_kernels_once(
        topo, compiled_kernels):
    """The whole train step of the benchmark's ``evabyte-L4.pretrain-16k-b1``
    cell (its ``model`` as the configuration file has it, ``remat`` on, one
    sequence of 16,384 bytes and its target): it compiles, which a program
    too large for the chip does not; what a block keeps under ``remat``
    (``models/transformer.py`` ``_REMAT_KEEPS``) leaves each forward kernel
    one call a layer; no fusion both makes one of the 28 dense leaves'
    weight gradients and writes its AdamW state (``separates_update``); and
    the compiler's count of its memory stays under
    ``EVABYTE_STEP_GIB``, so that a later change's extra temporary cannot
    push the cell out of the chip's memory unseen."""
    from mpi_tpu.models import TransformerConfig, make_mesh_nd

    with open(os.path.join(os.path.dirname(__file__), os.pardir, "benchmark",
                           "configs", "evabyte-L4.json")) as f:
        model = json.load(f)["model"]
    seq = 16384
    cfg = TransformerConfig(**dict(model, dtype=jnp.dtype(model["dtype"]),
                                   max_seq=seq + 1))
    assert cfg.remat
    mesh = make_mesh_nd(1, devices=topo.devices[:1])
    compiled = _compile(*_step_args(cfg, mesh, 1, seq),
                        names=FLASH_KERNELS + EVA_KERNELS + LAYER_SCOPES)
    text = compiled.as_text()
    for kernel in ("flash_fwd", "eva_remote_fwd"):
        calls = len(re.findall(rf"%{kernel}(\.\d+)? = ", text))
        assert calls == cfg.n_layers, \
            f"{calls} %{kernel} calls for {cfg.n_layers} layers"
    # Each dense leaf's weight gradient is a product of its own, and its
    # AdamW update a pass that holds none (``separates_update``): 8 FFN
    # matrices of [4096, 11008], 4 of [11008, 4096], 12 q/k/v and 4 wo.
    triples = _state_triples(text)
    dense = {"f32[4096,11008]": 8, "f32[11008,4096]": 4,
             "f32[4096,32,128]": 12, "f32[32,128,4096]": 4}
    assert {s: triples[s, False] for s in dense} == dense, triples
    assert not any(triples[s, True] for s in dense), triples
    mem = compiled.memory_analysis()
    gib = (mem.argument_size_in_bytes + mem.output_size_in_bytes
           + mem.temp_size_in_bytes - mem.alias_size_in_bytes) / 2 ** 30
    assert gib < EVABYTE_STEP_GIB, (
        f"the compiler counts {gib:.3f} GiB for the step, over the "
        f"{EVABYTE_STEP_GIB} GiB this case allows, which leaves "
        f"{CHIP_GIB - EVABYTE_STEP_GIB:.2f} GiB of the chip's {CHIP_GIB} for "
        f"the benchmark's correctness program (up to 0.24 GiB larger) and "
        f"what else the process holds")


SSD_KERNELS = ("%ssd_fwd", "%ssd_bwd")


@contextlib.contextmanager
def _scans_counted():
    """Tracing on; yields a dict that holds, on leaving, what the
    ``ssd.scans.*`` counters rose by inside."""
    from mpi_tpu.utils import trace

    was, before, rose = trace.enabled(), dict(trace.counters()), {}
    trace.enable()
    try:
        yield rose
        rose.update({k: v - before.get(k, 0)
                     for k, v in trace.counters().items()
                     if k.startswith("ssd.scans.") and v != before.get(k, 0)})
    finally:
        if not was:
            trace.disable()


def test_ssd_kernels_compile_at_the_nemotron_cell_shapes(one_chip):
    """The Mamba-2 scan, forward and backward, at the shapes the benchmark's
    ``nemotron-3-nano-L9-E8.pretrain-8k`` cell calls it with (batch 2 x
    8,192, 64 heads of 64 in 8 groups, a state of 128, chunks of 128,
    bfloat16): ``ssd_scan`` hands a TPU lowering its two kernels without
    being told to (the backend here is the CPU), every input has its
    gradient, and the call is counted by what its shapes decide."""
    from mpi_tpu.ops.ssd import ssd_scan

    b, s, h, p, g, n, chunk = 2, 8192, 64, 64, 8, 128, 128

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(*inputs):
        return jnp.sum(ssd_scan(*inputs, chunk).astype(jnp.float32))

    with _scans_counted() as took:
        compiled = _compile(
            jax.grad(loss, argnums=tuple(range(6))),
            shaped((b, s, h, p), jnp.bfloat16), shaped((b, s, h), jnp.float32),
            shaped((h,), jnp.float32), shaped((b, s, g, n), jnp.bfloat16),
            shaped((b, s, g, n), jnp.bfloat16), shaped((h,), jnp.float32),
            names=SSD_KERNELS)
    assert took == {"ssd.scans.kernel": 1}
    text = compiled.as_text()
    assert " while(" not in text  # the program's recurrence over chunks


def test_ssd_kernels_run_on_each_chips_rows_of_a_dp_batch(topo):
    """dp 4 on the described 2x2: GSPMD cannot partition a Mosaic kernel,
    and left to it every chip would gather ``x``, ``B``, ``C`` and ``y``'s
    gradient and scan the whole batch. The mixer runs the scan per shard,
    so the step's kernels take one chip's rows (batch 4 / dp 4 = 1) and
    nothing is gathered for them."""
    from mpi_tpu.models import TransformerConfig, make_mesh_nd

    batch, seq, heads, hd, groups, n = 4, 256, 4, 64, 2, 128
    cfg = TransformerConfig(
        vocab=256, d_model=128, n_heads=2, n_layers=2, d_ff=256,
        layer_pattern="MM", max_seq=seq + 1, dtype=jnp.bfloat16,
        position_table=False, ssm_heads=heads, ssm_head_dim=hd,
        ssm_groups=groups, ssm_state=n, ssm_conv=4, ssm_chunk=128, remat=True)
    mesh = make_mesh_nd(4, axes=("dp",), devices=topo.devices)
    assert dict(mesh.shape) == {"dp": 4}
    text = _compile(*_step_args(cfg, mesh, batch, seq),
                    names=SSD_KERNELS + ("ssm.scan",)).as_text()
    assert "all-gather" not in text
    assert "all-reduce" in text                 # the dp gradients
    local = f"bf16[{batch // 4},{seq},{heads * hd}]"
    for kernel in ("ssd_fwd", "ssd_bwd"):
        calls = re.findall(rf"%{kernel}(?:\.\d+)? = .*", text)
        assert calls and all("ssm.scan" in call for call in calls), kernel
        assert all(local in call and f"bf16[{batch}," not in call
                   for call in calls), calls[0][:400]


def _layout_of_the_routed_loops(cfg, batch, seq):
    """The shape the routed share's backward loop writes its rows of the
    input's gradient to: the tile layout's rows and the zero row, float32
    (``models/moe.py`` ``_layout_rows``)."""
    from mpi_tpu.models import moe

    tokens = batch * seq
    floor = moe.floor_tiles(tokens, cfg.moe_top_k, cfg.moe_experts_held,
                            cfg.n_experts)
    rows = moe._layout_rows(tokens, cfg.moe_top_k, cfg.moe_experts_held,
                            floor)
    return f"f32[{rows + 1},{cfg.d_model}]"


# What the compiler counts for the nemotron-3-nano cell's step: 11.783 GiB
# at batch 2 with the in-projection's product held under ``remat`` and the
# routed share's tile layouts (13.349 with every value an ``M`` or ``E``
# block names held, the table above ``_REMAT_KEEPS``).
NEMOTRON_STEP_GIB = 15.25


def test_nemotron_cell_train_step_fits_with_room_to_spare(
        topo, compiled_kernels):
    """The whole train step of the benchmark's
    ``nemotron-3-nano-L9-E8.pretrain-8k`` cell (its ``model`` as the
    configuration file has it: the pattern ``MEMEM*EME`` at published
    widths, ``remat`` on; its traffic's batch of sequences of 8,192 and
    their targets): it compiles for one chip, with the flash kernels of
    the attention layer and the scopes a trace splits it by, and the
    compiler's count
    of its memory leaves at least 0.5 GiB of the chip's 15.75."""
    from mpi_tpu.models import TransformerConfig, make_mesh_nd

    bench = os.path.join(os.path.dirname(__file__), os.pardir, "benchmark")
    with open(os.path.join(bench, "configs",
                           "nemotron-3-nano-L9-E8.json")) as f:
        model = json.load(f)["model"]
    with open(os.path.join(bench, "traffic", "pretrain-8k.json")) as f:
        traffic = json.load(f)
    batch, seq = traffic["batch"], traffic["seq"]
    cfg = TransformerConfig(**dict(model, dtype=jnp.dtype(model["dtype"]),
                                   max_seq=seq + 1))
    assert cfg.remat and cfg.layer_pattern == "MEMEM*EME"
    mesh = make_mesh_nd(1, devices=topo.devices[:1])
    with _scans_counted() as took:
        compiled = _compile(
            *_step_args(cfg, mesh, batch, seq),
            names=FLASH_KERNELS + SSD_KERNELS + LAYER_SCOPES + (
                "ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.norm",
                "ssm.out_proj", "moe.route", "moe.routed", "moe.shared"))
    text = compiled.as_text()
    mixers = cfg.layer_pattern.count("M")
    # Counted as ``ssd_scan_flat`` is traced, and ``jax.checkpoint`` traces
    # a kind's block once for all its layers of one shape (``ssm.layers``
    # reads 1 here too; without ``remat`` both read the four mixers).
    assert took == {"ssd.scans.kernel": 1}
    # ``_REMAT_KEEPS`` holds nothing of the scan (the table above it), so
    # the backward runs the forward kernel again for the states.
    for kernel, a_mixer in (("ssd_fwd", 2), ("ssd_bwd", 1)):
        calls = re.findall(rf"%{kernel}(?:\.\d+)? = .*", text)
        assert len(calls) == a_mixer * mixers, \
            f"{len(calls)} %{kernel} calls for {mixers} mixers"
        assert all("ssm.scan" in call for call in calls), kernel
    # What an ``M`` block holds (``_REMAT_KEEPS``: ``ssm_in``) the backward
    # does not redo: the step has the in-projection's product
    # ``bf16[2,8192,10304]`` once a mixer forward and none in a block's
    # recomputation, where the parent's had one a mixer there.
    products = [line for line in text.splitlines()
                if "/ssm.in_proj/bsd,de->bse/dot_general" in line]
    assert any("/jvp(" in op for op in products)
    redone = [op for op in products if "rematted_computation" in op]
    assert not redone, redone[0][:300]
    assert _layout_of_the_routed_loops(cfg, batch, seq) in text
    mem = compiled.memory_analysis()
    gib = (mem.argument_size_in_bytes + mem.output_size_in_bytes
           + mem.temp_size_in_bytes - mem.alias_size_in_bytes) / 2 ** 30
    print(f"nemotron cell step: the compiler counts {gib:.3f} GiB")
    assert NEMOTRON_STEP_GIB <= CHIP_GIB - 0.5
    assert gib < NEMOTRON_STEP_GIB, (
        f"the compiler counts {gib:.3f} GiB for the step at batch {batch}, "
        f"over the {NEMOTRON_STEP_GIB} GiB that leave 0.5 of the chip's "
        f"{CHIP_GIB}")


def test_flash_kernels_compile_at_head_dim_64(one_chip):
    """Causal, default blocks, forward and backward, at the shape the
    benchmark's ``lfm2-24b-a2b-L9-E8.pretrain-8k`` cell calls the kernels
    with (batch 2 x 8,192, 32 query heads on 8 kv heads of 64): the
    ``(1, block, 64)`` blocks take the arrays' whole last dimension."""
    from mpi_tpu.ops import flash_attention

    def loss(q, k, v):
        out = flash_attention(q, k, v, True, None, None, False)
        return jnp.sum(out.astype(jnp.float32))

    q = jax.ShapeDtypeStruct((2, 8192, 32, 64), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((2, 8192, 8, 64), jnp.bfloat16,
                              sharding=one_chip)
    _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv,
             names=FLASH_KERNELS)


# What the compiler counts for the lfm2-24b-a2b cell's step: 12.448 GiB at
# batch 2 with ``remat`` on and the routed share's tile layouts, so the cut
# is ``layer_types[1:10]`` and not a shorter stack.
LFM2_STEP_GIB = 15.25


def test_lfm2_cell_train_step_fits_with_room_to_spare(topo, compiled_kernels):
    """The whole train step of the benchmark's
    ``lfm2-24b-a2b-L9-E8.pretrain-8k`` cell (its ``model`` as the
    configuration file has it: the pattern ``CF*ECECECE*ECECECE`` at
    published widths, heads of 64 through the flash kernels, ``remat`` on;
    its traffic's batch of sequences of 8,192 and their targets): it
    compiles for one chip, with the flash kernels and the scopes a trace
    splits it by and no shared expert's, and the compiler's count of its
    memory leaves at least 0.5 GiB of the chip's 15.75."""
    from mpi_tpu.models import TransformerConfig, make_mesh_nd

    bench = os.path.join(os.path.dirname(__file__), os.pardir, "benchmark")
    with open(os.path.join(bench, "configs",
                           "lfm2-24b-a2b-L9-E8.json")) as f:
        model = json.load(f)["model"]
    with open(os.path.join(bench, "traffic", "pretrain-8k.json")) as f:
        traffic = json.load(f)
    batch, seq = traffic["batch"], traffic["seq"]
    cfg = TransformerConfig(**dict(model, dtype=jnp.dtype(model["dtype"]),
                                   max_seq=seq + 1))
    assert cfg.remat and cfg.head_dim == 64
    mesh = make_mesh_nd(1, devices=topo.devices[:1])
    compiled = _compile(
        *_step_args(cfg, mesh, batch, seq),
        names=FLASH_KERNELS + LAYER_SCOPES + (
            "shortconv.in_proj", "shortconv.conv", "shortconv.out_proj",
            "moe.route", "moe.routed"))
    text = compiled.as_text()
    assert "moe.shared" not in text
    # ``attn_out`` / ``attn_lse`` are held: one forward kernel a layer.
    calls = len(re.findall(r"%flash_fwd(\.\d+)? = ", text))
    assert calls == cfg.layer_pattern.count("*"), calls
    assert _layout_of_the_routed_loops(cfg, batch, seq) in text
    mem = compiled.memory_analysis()
    gib = (mem.argument_size_in_bytes + mem.output_size_in_bytes
           + mem.temp_size_in_bytes - mem.alias_size_in_bytes) / 2 ** 30
    print(f"lfm2 cell step: the compiler counts {gib:.3f} GiB")
    assert LFM2_STEP_GIB <= CHIP_GIB - 0.5
    assert gib < LFM2_STEP_GIB, (
        f"the compiler counts {gib:.3f} GiB for the step at batch {batch}, "
        f"over the {LFM2_STEP_GIB} GiB that leave 0.5 of the chip's "
        f"{CHIP_GIB}")


@pytest.fixture(scope="module")
def ring_mesh(topo):
    return Mesh(np.asarray(topo.devices), ("rank",))


def _per_chip(mesh, *lead):
    """(1024, 256) float32 per chip, stacked on a rank-sharded axis 0."""
    return jax.ShapeDtypeStruct((*lead, 256), jnp.float32,
                                sharding=NamedSharding(mesh, P("rank")))


def test_ring_allgather_compiles_four_chips(ring_mesh):
    from mpi_tpu.ops.ring_collectives import ring_allgather_sharded

    _compile(lambda x: ring_allgather_sharded(x, ring_mesh, interpret=False),
             _per_chip(ring_mesh, 4 * 1024))


def test_ring_allreduce_compiles_four_chips(ring_mesh):
    from mpi_tpu.ops.ring_collectives import ring_allreduce_sharded

    _compile(lambda x: ring_allreduce_sharded(x, ring_mesh, interpret=False),
             _per_chip(ring_mesh, 4, 1024))


def test_pallas_sendrecv_compiles_four_chips(ring_mesh):
    from mpi_tpu.parallel.p2p import pallas_sendrecv_sharded

    ring = [(r, (r + 1) % 4) for r in range(4)]
    _compile(lambda x: pallas_sendrecv_sharded(x, ring_mesh, ring,
                                               interpret=False),
             _per_chip(ring_mesh, 4 * 1024))
