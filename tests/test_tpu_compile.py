"""The main path's Pallas kernels, compiled by the real TPU compiler.

Every other kernel test runs in Pallas interpret mode on the CPU, which
accepts programs the chip's compiler refuses (a block shape off the
(8, 128) tile, an unsupported vector layout, too much VMEM). The TPU
compiler is installed here and compiles for a chip that is *described*
and not attached (`v5e:2x2`), so these cases guard every later change to
a kernel at no chip time. Nothing runs: a pass says the compiler accepts
the kernel at this shape, not that its numbers are right — the
interpret-mode parity suites and `chip_smoke.py` say that.
"""
import collections
import functools
import dataclasses
import importlib
import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from determined_tpu.ops.paged_attention import paged_attention

# The module, not the function `determined_tpu.ops` re-exports under its name.
fa = importlib.import_module("determined_tpu.ops.flash_attention")

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def chips():
    """The four described chips of a v5e 2x2; the module is skipped where
    the topology cannot be described (no TPU compiler in the
    installation)."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any refusal means "not here"
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    return topo.devices


@pytest.fixture(scope="module")
def chip(chips):
    """One described v5e chip."""
    return SingleDeviceSharding(chips[0])


@pytest.fixture(autouse=True)
def _for_the_chip(monkeypatch):
    """Take the kernels' TPU branch (the process's own backend is the
    CPU), with the persistent compile cache off: an entry compiled for a
    described chip is written but can never be read back here, and the
    next compile would only warn about it."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(fa, "_use_pallas", lambda: True)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _flash_train(s, heads=12, head_dim=64, block=1024):
    """Forward + all three gradients at batch 1 x 12 heads x 64 (1k rides
    the GPT-2 bench batch of 24 instead: BH 288)."""
    b = 24 if s == 1024 else 1
    qkv = [((b, s, heads, head_dim), BF16)] * 3

    def step(q, k, v):
        def loss(q, k, v):
            o = fa.flash_attention(
                q, k, v, causal=True, block_q=block, block_k=block
            )
            return jnp.sum(o.astype(jnp.float32))

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    return step, qkv


def _packed_prefill():
    """serving prefill: 4 rows x 512 packed prompts, segment ids."""
    def step(q, k, v, seg):
        return fa.flash_attention(
            q, k, v, causal=True, block_q=512, block_k=512, segment_ids=seg
        )

    return step, [((4, 512, 12, 64), BF16)] * 3 + [((4, 512), jnp.int32)]


def _cached_prefill():
    """prefix-cache hit: 512 tail tokens attend through 1024 cached ones
    at `kv_offset` (the GPT.prefill_kv_cached geometry)."""
    def step(q, k, v, seg, kv_seg):
        return fa.flash_attention(
            q, k, v, causal=True, kv_offset=1024, block_q=512,
            block_k=fa.fit_block(1536, 1024), segment_ids=seg,
            kv_segment_ids=kv_seg,
        )

    return step, [
        ((4, 512, 12, 64), BF16), ((4, 1536, 12, 64), BF16),
        ((4, 1536, 12, 64), BF16), ((4, 512), jnp.int32),
        ((4, 1536), jnp.int32),
    ]


def _gather_decode(q_n):
    """the engine's gather fallback as `GPT.decode_kv` runs it on the
    chip (`q_pad=8`) at the default serving geometry, 8 pages x 128 a
    slot: Q = 1 is the plain decode step, Q = 5 a draft of four."""
    from determined_tpu.models import gpt as gpt_mod

    model = gpt_mod.GPT(dataclasses.replace(
        gpt_mod.tiny(1024), n_layers=2, n_heads=12, d_model=768,
        attn_impl="flash", dtype=BF16))
    leaves, tree = jax.tree.flatten(
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))))

    def step(*args):
        params = jax.tree.unflatten(tree, args[:len(leaves)])
        return model.decode_kv(
            params, *args[len(leaves):], q_pad=8, kernel="gather")

    pool = ((2, 129, 128, 12, 64), BF16)
    slot = ((8,), jnp.int32)
    return step, [(a.shape, a.dtype) for a in leaves] + [
        ((8, q_n), jnp.int32), slot, slot, ((8,), jnp.bool_), pool, pool,
        ((8, 8), jnp.int32),
    ]


def _paged_decode(n_heads, head_dim, block_h=None):
    """A small serving pool (129 pages x 128, batch 8, 8 pages a slot,
    8 query rows) with `q_lens` given — the speculative-verify call."""
    step = functools.partial(paged_attention, block_h=block_h)
    pool = ((129, 128, n_heads, head_dim), BF16)
    slot = ((8,), jnp.int32)

    def call(q, kp, vp, pt, lengths, active, q_lens):
        return step(q, kp, vp, pt, lengths, active, q_lens=q_lens)

    return call, [
        ((8, 8, n_heads, head_dim), BF16), pool, pool,
        ((8, 8), jnp.int32), slot, slot, slot,
    ]


def _gdn_recurrence(grad):
    """The chunked delta rule's recurrence over chunks at
    `qwen3next-train-8k-ep16share`'s shape: 32 value heads on 16 key
    heads of 128, 64 chunks of 128 tokens. The forward as the step's
    forward runs it, or the forward that stashes the state entering each
    chunk and the backward, whose body is `jax.vjp` of the chunk step."""
    from determined_tpu.ops import gated_delta as gd

    def forward(*ops):
        return gd._recurrence_kernels(*ops, False)

    def gradients(*ops):
        return jax.grad(lambda *a: jnp.sum(forward(*a).astype(jnp.float32)),
                        argnums=tuple(range(6)))(*ops)

    hv, hk, n, c, d = 32, 16, 64, 128, 128
    return gradients if grad else forward, [
        ((hv, n, c, d), BF16), ((hv, n, c, d), BF16), ((hv, n, c, c), BF16),
        ((hk, n, c, d), BF16), ((hk, n, c, d), BF16),
        ((hv, n, 1, c), jnp.float32)]


CASES = {
    "gdn_recurrence_8k_forward": lambda: _gdn_recurrence(False),
    "gdn_recurrence_8k_stash_and_backward": lambda: _gdn_recurrence(True),
    "flash_train_1k_mono": lambda: _flash_train(1024),
    "flash_train_1k_mono_d128": lambda: _flash_train(1024, 16, 128),
    "flash_train_16k_fused_vmem_dq": lambda: _flash_train(16384),
    "flash_train_32k_fused_vmem_dq": lambda: _flash_train(32768),
    "flash_train_32k_d256_split": lambda: _flash_train(32768, 2, 256, 512),
    # the 8k cells' attention: GLM's 20 latent heads, Qwen's 16 query heads
    "flash_train_8k_glm_20x256": lambda: _flash_train(8192, 20, 256, 512),
    "flash_train_8k_qwen_16x256": lambda: _flash_train(8192, 16, 256, 512),
    "packed_prefill_4x512_segments": _packed_prefill,
    "cached_prefill_kv_offset": _cached_prefill,
    "gather_decode_q1_8x128": lambda: _gather_decode(1),
    "gather_decode_q5_8x128": lambda: _gather_decode(5),
    "paged_decode_12x64": lambda: _paged_decode(12, 64),
    "paged_decode_12x64_block_h_2": lambda: _paged_decode(12, 64, 2),
    "paged_decode_16x128": lambda: _paged_decode(16, 128),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(chip, case):
    step, shapes = CASES[case]()
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
        for shape, dtype in shapes
    ]
    compiled = jax.jit(step).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), (
        "compiled without a Mosaic kernel: the reference path was taken"
    )


def _flash_kernels(text):
    """The Mosaic operations of a compiled program that
    `benchmark/kernels/flash_*.json` match, counted by kernel."""
    from benchmark import kernel_events, trace_reduce

    found = collections.Counter()
    for line in text.splitlines():
        if trace_reduce.MOSAIC in line and " custom-call(" in line:
            op = trace_reduce.op_name(line.strip())
            found.update(k for k in ("flash_forward", "flash_backward")
                         if re.search(kernel_events.kernel(k)["pattern"], op))
    return found


@pytest.mark.parametrize("case,backwards", [
    ("flash_train_8k_glm_20x256", 1),
    ("flash_train_8k_qwen_16x256", 1),
    ("flash_train_32k_d256_split", 2),   # a head's dq passes the budget
])
def test_blocked_backward_is_one_kernel_where_dq_fits(chip, case, backwards):
    """At the 8k cells' attention shapes (batch 1, S 8192, d 256, block
    512) the blocked backward compiles, its VMEM limit raised for the
    whole head's fp32 dq, as ONE Mosaic kernel under the name
    `flash_backward.json` matches, and the program holds no fp32
    [heads, key blocks, S, d] partials of dq. Past the budget the split
    pair's two kernels run, under that name too."""
    step, shapes = CASES[case]()
    (_, s, heads, d), _ = shapes[0]
    text = jax.jit(step).lower(*[
        jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
        for shape, dtype in shapes]).compile().as_text()
    assert dict(_flash_kernels(text)) == {
        "flash_forward": 1, "flash_backward": backwards}
    assert f"f32[{heads},{s // 512},{s},{d}]" not in text


# -- the names the flash kernels have in a trace ------------------------------
def _gpt_grad(mesh, layer_loop):
    """The gradient of a small GPT's loss, flash attention on, under the
    train step's scopes as `GPT` opens them."""
    from determined_tpu.models import gpt as gpt_mod

    model = gpt_mod.GPT(dataclasses.replace(
        gpt_mod.tiny(256), d_model=256, attn_impl="flash", dtype=BF16,
        layer_loop=layer_loop, remat=True), mesh=mesh)
    rng = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda: model.init(rng))

    def grad(params, tokens):
        return jax.grad(
            lambda p: model.loss(p, {"tokens": tokens}, rng)[0])(params)

    return grad, params, jax.ShapeDtypeStruct((4, 256), jnp.int32)


def _gpt_grad_text(mesh, layer_loop, sharding):
    """`_gpt_grad`'s program as compiled for the described chips."""
    grad, params, tokens = _gpt_grad(mesh, layer_loop)
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        (params, tokens))
    return jax.jit(grad).lower(*args).compile().as_text()


@pytest.mark.parametrize("n_chips,layer_loop,want", [
    (1, "unroll", {"flash_forward", "flash_backward"}),   # small-train-1k
    (4, "scan", {"flash_sharded"}),                       # xl-train-fsdp4
], ids=["one-chip", "mesh"])
def test_flash_kernels_keep_the_names_the_benchmark_matches(
        chips, n_chips, layer_loop, want):
    """XLA names a Pallas kernel's operation after the innermost
    component of jax's name stack. `benchmark/kernels/flash_*.json`
    (`flash_time_share`, `flash_roofline`, `scope_reduce`) find the
    kernels by the names that gives in the benchmark's two training
    cells: a `name=` on a `pallas_call`, or a `jax.named_scope` left open
    around the attention call, renames them and fails here."""
    from jax.sharding import NamedSharding, PartitionSpec

    from benchmark import kernel_events, trace_reduce
    from determined_tpu.parallel.mesh import MeshConfig, make_mesh

    if n_chips == 1:
        mesh, sharding = None, SingleDeviceSharding(chips[0])
    else:
        mesh = make_mesh(MeshConfig(data=1, fsdp=n_chips), chips)
        sharding = NamedSharding(mesh, PartitionSpec())
    text = _gpt_grad_text(mesh, layer_loop, sharding)
    # as the benchmark prints a trace's events: `mosaic:<instruction>`
    ops = {trace_reduce.op_name(line.strip()) for line in text.splitlines()
           if trace_reduce.MOSAIC in line}
    assert ops, "compiled without a Mosaic kernel"
    found = {op: {k for k in ("flash_forward", "flash_backward",
                              "flash_sharded")
                  if re.search(kernel_events.kernel(k)["pattern"], op)}
             for op in ops}
    assert all(found.values()), found
    assert set().union(*found.values()) == want, found


# -- what stands between the projections and the flash kernels ---------------
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%([\w.\-]+) = .*? ([a-z][a-z\-]*)\(((?:%|\)).*)$")
_FREE = ("bitcast", "reshape", "get-tuple-element")   # move no data


def _layout_ops_at_kernels(text):
    """The `copy` / `transpose` instructions of a compiled program's entry
    computation that feed a Mosaic kernel or read what one wrote, looking
    through the instructions that move no data."""
    from benchmark import trace_reduce

    entry = text[text.index("ENTRY "):]
    ops, operands = {}, {}
    for line in entry.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            name, op, rest = m.groups()
            ops[name] = "kernel" if trace_reduce.MOSAIC in line else op
            operands[name] = re.findall(r"%([\w.\-]+)", rest.split(")")[0])
    users = {}
    for name, args in operands.items():
        for arg in args:
            users.setdefault(arg, []).append(name)

    def reach(name, edges):
        for nxt in edges.get(name, ()):
            if ops.get(nxt) in _FREE:
                yield from reach(nxt, edges)
            elif ops.get(nxt) in ("copy", "transpose"):
                yield nxt

    return sorted({found for name, op in ops.items() if op == "kernel"
                   for edges in (operands, users)
                   for found in reach(name, edges)})


def test_nothing_is_copied_between_projections_and_flash_kernels(chips):
    """GPT's attention half hands the monolithic kernels its fused
    projection as XLA lays it out ([B, 3, H*D, S]: the sequence on the
    lanes) and takes o^T and the one gradient back the same way, so the
    compiled train step has no layout copy at a flash kernel. Through PR
    26 it had eight a layer (q, k, v in and o out of [BH, S, D], and the
    same for their gradients): 16 in this two-layer program."""
    text = _gpt_grad_text(None, "unroll", SingleDeviceSharding(chips[0]))
    assert text.count("tpu_custom_call") == 4, "two layers, two passes"
    assert _layout_ops_at_kernels(text) == []


# -- the non-finite guard in the compiled train step --------------------------
_F32_COPY = re.compile(r" = f32\[([\d,]*)\]\S* copy\(")


def _train_step(monkeypatch, chips, workload, n_layers=2):
    """A benchmark cell's train step (`Trainer._build_step_fn()` under
    `SyntheticTrial`'s AdamW + clip, on the cell's mesh) compiled for the
    described chips by `benchmark/tools/size_cells.py::train` itself, at
    the cell's widths, `n_layers` deep and on one short row a chip (the
    update does not depend on the batch; the compile's seconds do): the
    program's text, and the matrices its optimizer state is made of by
    the number of elements a chip holds of each (fsdp shards one
    dimension, whichever it is)."""
    import types

    from benchmark.model import gpt_config_kwargs
    from benchmark.run import Cell
    from benchmark.tools import size_cells
    from determined_tpu.models.gpt import GPT, GPTConfig

    cell = Cell(workload)
    cell.config = dict(cell.config, n_layer=n_layers)
    cell.traffic = dict(cell.traffic, seq_len=128)
    monkeypatch.setattr(
        size_cells, "sizes", lambda lowered: lowered.compile().as_text())
    text = size_cells.train(
        cell, types.SimpleNamespace(devices=chips), global_batch=cell.chips)
    assert text.count("tpu_custom_call") == 2 * n_layers, "flash not taken"
    params = jax.eval_shape(
        GPT(GPTConfig(**gpt_config_kwargs(cell.config))).init,
        jax.random.PRNGKey(0))
    matrices = dict(params["blocks"], tok_embed=params["tok_embed"])
    return text, {
        matrices[name].size // cell.chips: name
        for name in ("wqkv", "wo", "wi", "wo_mlp", "tok_embed")}


@pytest.mark.parametrize("workload", ["small-train-1k", "xl-train-fsdp4"])
def test_guard_leaves_no_conditional_and_no_copy_of_the_state(
        monkeypatch, chips, workload):
    """The non-finite guard selects leaf by leaf, fused into the
    optimizer's update, so the compiled train step has no `conditional`
    and no computation of it copies an fp32 array the size of a chip's
    share of a weight matrix or of `tok_embed`. Through PR 30 the guard
    was a `lax.cond` over the whole state: XLA sank the AdamW update into
    its branch, ran it in another layout, and copied parameters, moments
    and gradients in and out of it, 21 such copies a step in
    `xl-train-fsdp4` and 10 in `small-train-1k` at full depth (15 and 10
    in these two-layer programs). The compiler's own prefetches
    (`copy-start`) and `pos_embed`'s transposes are not the subject."""
    text, by_size = _train_step(monkeypatch, chips, workload)
    found = collections.Counter({"conditional": text.count(" conditional(")})
    for shape in _F32_COPY.findall(text):
        size = math.prod(int(d) for d in shape.split(",") if d)
        if size in by_size:
            found[by_size[size]] += 1
    assert dict(found) == {"conditional": 0}


# -- the cell of another architecture (ISSUE 32) -------------------------------
_QWEN_STEP = {}


def _qwen_step(monkeypatch, chips):
    """`qwen3next-train-8k-ep16share`'s train step (`Trainer`'s own, built
    as the cell's driver builds it, by `benchmark/tools/size_train_lm.py`)
    compiled for a described v5e at the published widths, one period deep
    as the cell is, on one row of 4096 tokens with 4 experts held and 2048
    rows of vocabulary (the compile's seconds follow those; the program's
    structure does not): its text, the bytes of its temporaries and the
    cell, compiled once a module."""
    import types

    from benchmark.run import Cell
    from benchmark.tools import size_cells, size_train_lm

    if not _QWEN_STEP:
        cell = Cell("qwen3next-train-8k-ep16share")
        cell.config = dict(cell.config, num_experts=4, vocab_size=2048)
        cell.traffic = dict(cell.traffic, seq_len=4096)
        monkeypatch.setattr(size_cells, "sizes", lambda low: low.compile())
        compiled = size_train_lm.train(
            cell, types.SimpleNamespace(devices=chips), global_batch=1)
        _QWEN_STEP.update(
            cell=cell, text=compiled.as_text(),
            temp=compiled.memory_analysis().temp_size_in_bytes)
    return types.SimpleNamespace(**_QWEN_STEP)


def test_qwen3_next_cells_step_compiles_with_no_conditional(
        monkeypatch, chips):
    """The chip's compiler takes the chunked delta rule, the blocked
    flash kernels at head width 256 (the sequence lies past one tile, so
    not the monolithic ones) and the grouped matmul (its own Mosaic
    kernel, sized by the rows routed at run time), and the step has no
    `conditional`: PR 31's guard selects, and a dropless expert layer
    needs none."""
    text = _qwen_step(monkeypatch, chips).text
    assert text.count(" conditional(") == 0
    assert "ragged-dot" in text, "the grouped matmul is not the TPU's own"
    # one attention layer: a blocked forward, and its backward
    from benchmark import kernel_events, trace_reduce
    ops = {trace_reduce.op_name(line.strip()) for line in text.splitlines()
           if trace_reduce.MOSAIC in line and "ragged-dot" not in line}
    found = {k for op in ops for k in ("flash_forward", "flash_backward")
             if re.search(kernel_events.kernel(k)["pattern"], op)}
    assert found == {"flash_forward", "flash_backward"}, ops


def test_qwen3_next_cells_step_runs_the_rule_as_two_named_kernels(
        monkeypatch, chips):
    """The chunked delta rule's recurrence over chunks is a Pallas kernel
    pair a layer (`ops/gated_delta.py`), named: an unnamed `pallas_call`
    under a `custom_vjp` gets the names `benchmark/kernels/flash_*.json`
    find the flash kernels by, and would be counted as flash attention.
    So in the cell's step the Mosaic operations that match no flash
    pattern (the grouped matmul's apart) are `gdn_recurrence_fwd`, three
    layers' forward and the same repeated under `jax.checkpoint`, and
    `gdn_recurrence_bwd`; the one attention layer's forward and backward
    are found once each; both kernels carry `gdn_scan` in their name
    stack, for the scope readers; and no `while` is left under it (the
    `lax.scan` was nine of them, 64 trips each in the cell)."""
    from benchmark import kernel_events, lm_scope_reduce, trace_reduce

    text = _qwen_step(monkeypatch, chips).text
    flash, others, stacks = collections.Counter(), collections.Counter(), []
    for line in text.splitlines():
        if (trace_reduce.MOSAIC not in line or " custom-call(" not in line
                or "ragged-dot" in line):
            continue
        op = trace_reduce.op_name(line.strip())
        kinds = [k for k in ("flash_forward", "flash_backward")
                 if re.search(kernel_events.kernel(k)["pattern"], op)]
        if kinds:
            flash.update(kinds)
        else:
            others[trace_reduce.op_class(op)] += 1
            stacks.append(re.search(r'op_name="([^"]*)"', line).group(1))
    assert dict(flash) == {"flash_forward": 1, "flash_backward": 1}
    assert dict(others) == {"mosaic:gdn_recurrence_fwd": 6,
                            "mosaic:gdn_recurrence_bwd": 3}
    assert all("gdn_scan" in lm_scope_reduce.scopes_of(s) for s in stacks)
    whiles = [re.search(r'op_name="([^"]*)"', line).group(1)
              for line in text.splitlines() if " while(" in line]
    assert whiles, "no `while` at all (the expert layer's slabs): wrong pattern?"
    assert not [s for s in whiles if "gdn_scan" in s.split("/")]


def test_qwen3_next_cells_step_moves_no_row_buffer_whole(monkeypatch, chips):
    """The expert layer's row buffer is k x T rows of the hidden width
    for the worst case, and no instruction of the step gathers or widens
    a whole one: the two row permutations move slabs of the rows routed
    under a `while` (`ops/grouped_matmul.py`). Through PR 32 they were
    whole-buffer gathers, 5 a layer (20 here, and 20 of [81 920, 2048] in
    the cell), and the sum back widened the buffer to float32 first (8)."""
    step = _qwen_step(monkeypatch, chips)
    width = step.cell.config["hidden_size"]
    rows = (step.cell.config["num_experts_per_tok"]
            * step.cell.traffic["seq_len"])
    whole = re.compile(rf" = \w+\[{rows},{width}\]\S* (gather|convert)\(")
    found = collections.Counter(m.group(1) for m in whole.finditer(step.text))
    assert dict(found) == {}
    assert re.search(rf" = \w+\[\d+,{width}\]\S* gather\(", step.text), (
        "no gather of rows at all: wrong pattern?")
    # A `while`'s buffer cannot be rebuilt where it is read, as a gather
    # fusion's could, so the weights' gradients, which read two such
    # buffers a layer, must not be put off to the end of the step:
    # `grouped_matmul` ties them to the rows' gradients. By the
    # compiler's `memory_analysis()` here: 1.96 GiB of temporaries so,
    # 2.87 with them put off (at 8192 tokens and the cell's 32 experts
    # 5.19 and 10.35). At 1024 tokens, where this step was compiled
    # through PR 36, memory is not the scheduler's concern: with the
    # delta rule's kernels in place of nine `while`s it holds twelve row
    # buffers there where it held eight, tied or not (1.08 and 1.09 GiB).
    assert step.temp < 2.4 * 2 ** 30, step.temp / 2 ** 30


# -- the latent-attention cell (ISSUE 35) --------------------------------------
def test_glm47flash_cells_step_keeps_the_kernels_names(monkeypatch, chips):
    """`glm47flash-train-8k-ep8share`'s train step (`Trainer`'s own, built
    as the cell's driver builds it) compiled for a described v5e at the
    published widths, on one row of 1024 tokens with 2 experts held and
    2048 rows of vocabulary: the chip's compiler takes latent attention
    through the blocked flash kernels at 20 heads of 256 under the names
    `benchmark/kernels/flash_*.json` match (the MLA half's `checkpoint`
    and scopes close around the attention call; the MTP module's block
    brings a pair of its own), the grouped matmul is the TPU's own, and
    the step has no `conditional`."""
    import types

    from benchmark import kernel_events, trace_reduce
    from benchmark.drivers import train_lm_models
    from benchmark.run import Cell
    from benchmark.tools import size_cells, size_train_lm

    cell = Cell("glm47flash-train-8k-ep8share")
    cell.config = dict(cell.config, n_routed_experts=2, vocab_size=2048,
                       num_hidden_layers=2)
    cell.traffic = dict(cell.traffic, seq_len=1024)
    monkeypatch.setattr(size_cells, "sizes", lambda low: low.compile())
    with train_lm_models.as_train_lm():
        text = size_train_lm.train(
            cell, types.SimpleNamespace(devices=chips), global_batch=1
        ).as_text()
    assert text.count(" conditional(") == 0
    assert "ragged-dot" in text, "the grouped matmul is not the TPU's own"
    ops = collections.Counter(
        trace_reduce.op_name(line.strip()).split(".")[0]
        for line in text.splitlines()
        if trace_reduce.MOSAIC in line and " custom-call(" in line
        and "ragged-dot" not in line)
    found = collections.Counter()
    for op, n in ops.items():
        for k in ("flash_forward", "flash_backward"):
            if re.search(kernel_events.kernel(k)["pattern"], op):
                found[k] += n
    # 2 layers and the MTP module's block: three forwards, three backwards
    assert sum(ops.values()) == sum(found.values()), ops
    assert found["flash_forward"] == 3 and found["flash_backward"] == 3, ops
