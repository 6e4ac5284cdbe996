"""Pipeline parallelism: microbatch schedules over the `pipeline` axis.

TPU-native replacement for the reference's DeepSpeed PipelineModule path
(SURVEY.md §2.5: `use_pipeline_parallel`, pytorch/deepspeed/_deepspeed_context.py:241):
stage parameters live stacked along a leading `stage` axis sharded over the
mesh's `pipeline` axis; activations advance between neighbor devices with
`ppermute` inside a `lax.scan` over schedule ticks — fully compiled, no
host-side scheduling.

Two schedules:

- `pipeline_apply` — plain GPipe fill-drain. M microbatches over S stages
  take M + S - 1 ticks; bubble fraction (S-1)/(M+S-1). Each device computes
  its stage every tick (idle ticks compute-then-discard — branchless, which
  XLA prefers over data-dependent control flow).
- `circular_pipeline_apply` — interleaved/circular schedule (the
  Megatron-interleaved / praxis circular-pipeline idea): each device holds
  V *virtual* stages (device d runs global stages d, d+S, …, d+(V−1)·S) and
  activations loop the ring V times. For the same total layers the bubble
  shrinks from V·(S−1) stage-ticks to (S−1): fill-drain cost is paid once,
  not once per V-sized chunk.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax



def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    microbatches: jax.Array,
    *,
    axis_name: str = "pipeline",
) -> jax.Array:
    """Run microbatches through the pipeline; call inside shard_map.

    Args:
      stage_fn: params, activation [mb, ...] -> activation [mb, ...]. All
        stages must share one activation shape (standard transformer-block
        pipelining).
      stage_params: this device's stage parameters (leading `stage` axis of
        size 1 already squeezed by shard_map, or a plain per-stage pytree).
      microbatches: [M, mb, ...] — replicated across the pipeline axis; only
        stage 0 actually consumes it.

    Returns [M, mb, ...]: final-stage outputs, replicated across the axis.
    """
    n_stages = lax.axis_size(axis_name)
    stage_idx = lax.axis_index(axis_name)
    n_micro = microbatches.shape[0]
    ticks = n_micro + n_stages - 1
    fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def tick(carry, t):
        incoming, outputs = carry
        # Stage 0 picks up microbatch t (clamped); others use the activation
        # handed over by their neighbor last tick.
        mb_idx = jnp.clip(t, 0, n_micro - 1)
        x = jnp.where(
            stage_idx == 0,
            lax.dynamic_index_in_dim(microbatches, mb_idx, keepdims=False),
            incoming,
        )
        y = stage_fn(stage_params, x)
        # Last stage finished microbatch t - (n_stages - 1) this tick.
        out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
        valid = (t >= n_stages - 1) & (stage_idx == n_stages - 1)
        prev = lax.dynamic_index_in_dim(outputs, out_idx, keepdims=False)
        outputs = lax.dynamic_update_index_in_dim(
            outputs, jnp.where(valid, y, prev), out_idx, 0
        )
        # Hand activations to the next stage (ring; stage S-1 → 0 carries
        # garbage that stage 0 overwrites).
        incoming = lax.ppermute(y, axis_name, fwd_perm)
        return (incoming, outputs), None

    zero_act = jnp.zeros_like(microbatches[0])
    outputs0 = jnp.zeros_like(microbatches)
    (_, outputs), _ = lax.scan(
        tick, (zero_act, outputs0), jnp.arange(ticks)
    )
    # Replicate final-stage outputs to every pipeline rank: everyone else
    # contributed zeros, so a psum is a broadcast.
    outputs = jnp.where(stage_idx == n_stages - 1, outputs, jnp.zeros_like(outputs))
    return lax.psum(outputs, axis_name)


def circular_pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    microbatches: jax.Array,
    *,
    axis_name: str = "pipeline",
) -> jax.Array:
    """Interleaved (circular) schedule; call inside shard_map.

    Args:
      stage_fn: params, activation [mb, ...] -> activation [mb, ...].
      stage_params: this device's V virtual stages stacked on a leading
        axis — device d must hold global stages [v*S + d for v in range(V)]
        (round-robin assignment; `stack_circular_stages` builds the global
        layout).
      microbatches: [M, mb, ...], M >= S (device 0 re-injects a returned
        activation M − S ticks after it arrives; fewer microbatches would
        need it before the ring delivers it).

    Ticks: V·M + S − 1. At tick t device d serves injection idx = t − d
    (virtual stage idx//M, microbatch idx%M); the ring hands each finished
    circle back to device 0, which stashes it until its next-round slot or
    records it as output after round V−1.

    Returns [M, mb, ...] final outputs, replicated across the axis.
    """
    n_stages = lax.axis_size(axis_name)
    d = lax.axis_index(axis_name)
    v_stages = jax.tree.leaves(stage_params)[0].shape[0]
    n_micro = microbatches.shape[0]
    if n_micro < n_stages:
        raise ValueError(
            f"circular schedule needs microbatches ({n_micro}) >= pipeline "
            f"stages ({n_stages})"
        )
    total = v_stages * n_micro
    ticks = total + n_stages - 1
    fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def tick(carry, t):
        incoming, stash, outputs = carry
        idx = jnp.clip(t - d, 0, total - 1)   # injection this device serves
        v = idx // n_micro
        m = idx % n_micro
        inj = jnp.where(
            v == 0,
            lax.dynamic_index_in_dim(microbatches, m, keepdims=False),
            lax.dynamic_index_in_dim(stash, m, keepdims=False),
        )
        x = jnp.where(d == 0, inj, incoming)
        params_v = jax.tree.map(
            lambda p: lax.dynamic_index_in_dim(p, v, keepdims=False),
            stage_params,
        )
        y = stage_fn(params_v, x)
        incoming_next = lax.ppermute(y, axis_name, fwd)
        # The frame device 0 just received completed the circle for
        # injection t − (S−1); stash it for round v_r+1 or emit it.
        idx_r = t - (n_stages - 1)
        idx_rc = jnp.clip(idx_r, 0, total - 1)
        v_r = idx_rc // n_micro
        m_r = idx_rc % n_micro
        arrived = (idx_r >= 0) & (d == 0)
        final = v_r == v_stages - 1
        prev_stash = lax.dynamic_index_in_dim(stash, m_r, keepdims=False)
        stash = lax.dynamic_update_index_in_dim(
            stash, jnp.where(arrived & ~final, incoming_next, prev_stash),
            m_r, 0,
        )
        prev_out = lax.dynamic_index_in_dim(outputs, m_r, keepdims=False)
        outputs = lax.dynamic_update_index_in_dim(
            outputs, jnp.where(arrived & final, incoming_next, prev_out),
            m_r, 0,
        )
        return (incoming_next, stash, outputs), None

    zero = jnp.zeros_like(microbatches[0])
    (_, _, outputs), _ = lax.scan(
        tick,
        (zero, jnp.zeros_like(microbatches), jnp.zeros_like(microbatches)),
        jnp.arange(ticks),
    )
    # Outputs accumulate on device 0 (the circle's home); psum broadcasts.
    outputs = jnp.where(d == 0, outputs, jnp.zeros_like(outputs))
    return lax.psum(outputs, axis_name)


def one_f_one_b_stash_size(n_micro: int, n_stages: int) -> int:
    """In-flight activation stash entries per device under 1F1B: O(S), not
    O(M). Device d holds at most 2·(S−1−d)+1 stage inputs; the SPMD program
    is uniform across devices so the buffer is sized for device 0."""
    return min(n_micro, 2 * n_stages - 1)


def one_f_one_b_grads(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    emb_fn: Callable[..., jax.Array],
    emb_params: Any,
    loss_fn: Callable[[Any, jax.Array, jax.Array, jax.Array], Any],
    loss_params: Any,
    tokens_mb: jax.Array,
    mask_mb: jax.Array,
    *,
    targets_mb: Any = None,
    positions: Any = None,
    reduce_axes: tuple = (),
    axis_name: str = "pipeline",
):
    """1F1B schedule (memory-bounded pipelining); call inside shard_map.

    The capability the reference reached through DeepSpeed's PipeEngine
    (`/root/reference/examples/deepspeed/pipeline_parallelism/distributed.yaml`):
    forwards and backwards interleave per microbatch so each device stashes
    only O(S) stage inputs instead of GPipe's O(M). jax.grad of a
    forward-only scan cannot express that interleaving (autodiff replays all
    forwards, then all backwards), so this runs the whole fwd+bwd schedule
    explicitly — per-stage `jax.vjp` with stage-input recompute (remat) at
    backward time — and returns finished gradients; callers expose it to
    autodiff through `jax.custom_vjp` (models/gpt.py `_loss_1f1b`).

    Timing (device d, microbatch m, tick t of M + 2S − 2):
      forward  at t = m + d           (GPipe-rate fill)
      backward at t = m + 2(S−1) − d  (last stage seeds its own backward in
                                       the same tick its forward finishes)
    Each tick has one forward and one backward sub-slot, each ending in the
    collective ppermute every device must reach — warmup/drain sub-slots
    compute-and-discard (branchless, like `pipeline_apply`).

    Args:
      stage_fn: (params, x [mb, ...]) -> y, same shape. Differentiated via
        vjp per backward sub-slot, recomputing from the stashed input.
      emb_fn: (emb_params, tokens [mb, s], positions) -> x — microbatch
        producer, run on stage 0 (branchlessly everywhere; masked
        elsewhere).
      loss_fn: (loss_params, y, aux_tokens, mask) -> (objective,
        metric_sums) run on the last stage; `aux_tokens` is targets_mb's
        microbatch when given, else tokens_mb's (the loss shifts itself).
        `objective` MUST be a per-microbatch SUM (decomposable across
        microbatches): its unit-seeded cotangent starts each microbatch's
        backward independently; the caller rescales the returned grads
        afterwards (gradients are linear in the seed). When `reduce_axes`
        names manual mesh axes (sequence parallelism), loss_fn must psum
        its METRIC sums over them but keep the OBJECTIVE local: psum-ing
        the objective transposes into a psum of the unit cotangents and
        inflates every gradient by the axis size. Param grads (partials
        per shard) are psum'd over those axes exactly once, here.
      tokens_mb: [M, mb, s] int32; mask_mb: [M, mb, s] float32;
      targets_mb: [M, mb, s] int32 pre-shifted targets (aligned loss);
      positions: [s] int32 logical positions (permuted/sharded layouts).

    Returns (metric_sums, stage_grads, emb_grads, loss_grads): metric_sums /
    emb_grads / loss_grads psum-replicated over the pipeline axis;
    stage_grads per-device with a leading stacking axis of 1 (use out_spec
    P(axis_name)).
    """
    n_stages = lax.axis_size(axis_name)
    d = lax.axis_index(axis_name)
    n_micro = tokens_mb.shape[0]
    cap = one_f_one_b_stash_size(n_micro, n_stages)
    ticks = n_micro + 2 * n_stages - 2
    fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    bwd_perm = [(i, (i - 1) % n_stages) for i in range(n_stages)]

    def _masked_add(acc, delta, on):
        return jax.tree.map(
            lambda a, g: a + jnp.where(on, g, jnp.zeros_like(g)), acc, delta
        )

    def zeros_like_tree(tr):
        return jax.tree.map(jnp.zeros_like, tr)

    zero_act = jnp.zeros_like(emb_fn(emb_params, tokens_mb[0], positions))
    stash0 = jnp.zeros((cap,) + zero_act.shape, zero_act.dtype)
    # metric_sums shape comes from one abstract eval of loss_fn.
    aux_shape = jax.eval_shape(
        lambda: loss_fn(loss_params, zero_act, tokens_mb[0], mask_mb[0])[1]
    )
    msums0 = jnp.zeros(aux_shape.shape, aux_shape.dtype)

    def tick(carry, t):
        inc_f, inc_b, stash, msums, s_g, e_g, l_g = carry

        # -- forward sub-slot ------------------------------------------------
        f_idx = t - d
        f_on = (f_idx >= 0) & (f_idx < n_micro)
        mf = jnp.clip(f_idx, 0, n_micro - 1)
        tok_f = lax.dynamic_index_in_dim(tokens_mb, mf, keepdims=False)
        msk_f = lax.dynamic_index_in_dim(mask_mb, mf, keepdims=False)
        tgt_f = (
            tok_f if targets_mb is None
            else lax.dynamic_index_in_dim(targets_mb, mf, keepdims=False)
        )
        # lax.cond keeps edge-only work (embedding on stage 0, LM head on
        # the last stage) off the other devices — a real cost at vocab
        # scale. Legal under SPMD because the collectives (ppermutes) sit
        # outside the branches. NOTE: ring attention inside stage_fn puts a
        # ppermute INSIDE the stage compute, which every device runs every
        # tick (branchless), so the context collective stays uniform too.
        x_in = lax.cond(
            d == 0, lambda: emb_fn(emb_params, tok_f, positions),
            lambda: inc_f,
        )
        y = stage_fn(stage_params, x_in)
        slot = mf % cap
        prev = lax.dynamic_index_in_dim(stash, slot, keepdims=False)
        stash = lax.dynamic_update_index_in_dim(
            stash, jnp.where(f_on, x_in, prev), slot, 0
        )

        # Last stage: per-microbatch loss fwd+bwd — dy seeds this tick's
        # backward sub-slot for the same microbatch.
        def loss_vjp():
            obj, vjp_loss, aux = jax.vjp(
                lambda lp, yy: loss_fn(lp, yy, tgt_f, msk_f),
                loss_params, y, has_aux=True,
            )
            d_lp, dy = vjp_loss(jnp.ones_like(obj))
            return d_lp, dy, aux

        d_lp, dy, aux = lax.cond(
            d == n_stages - 1,
            loss_vjp,
            lambda: (zeros_like_tree(loss_params), jnp.zeros_like(y), msums0),
        )
        last_on = f_on & (d == n_stages - 1)
        msums = msums + jnp.where(last_on, aux, jnp.zeros_like(aux))
        l_g = _masked_add(l_g, d_lp, last_on)

        # -- backward sub-slot ----------------------------------------------
        b_idx = t - (2 * n_stages - 2 - d)
        b_on = (b_idx >= 0) & (b_idx < n_micro)
        mb_i = jnp.clip(b_idx, 0, n_micro - 1)
        cot_y = jnp.where(d == n_stages - 1, dy, inc_b)
        x_s = lax.dynamic_index_in_dim(stash, mb_i % cap, keepdims=False)
        _, vjp_stage = jax.vjp(stage_fn, stage_params, x_s)
        d_sp, dx = vjp_stage(cot_y)
        s_g = _masked_add(s_g, d_sp, b_on)

        # Stage 0's input cotangent is the embedding-output cotangent.
        def emb_vjp():
            tok_b = lax.dynamic_index_in_dim(tokens_mb, mb_i, keepdims=False)
            _, vjp_emb = jax.vjp(
                lambda ep: emb_fn(ep, tok_b, positions), emb_params
            )
            (d_ep,) = vjp_emb(dx)
            return d_ep

        d_ep = lax.cond(
            d == 0, emb_vjp, lambda: zeros_like_tree(emb_params)
        )
        e_g = _masked_add(e_g, d_ep, b_on & (d == 0))

        inc_f = lax.ppermute(y, axis_name, fwd_perm)
        inc_b = lax.ppermute(dx, axis_name, bwd_perm)
        return (inc_f, inc_b, stash, msums, s_g, e_g, l_g), None
    carry0 = (
        zero_act, zero_act, stash0, msums0,
        zeros_like_tree(stage_params), zeros_like_tree(emb_params),
        zeros_like_tree(loss_params),
    )
    (_, _, _, msums, s_g, e_g, l_g), _ = lax.scan(
        tick, carry0, jnp.arange(ticks)
    )
    msums = lax.psum(msums, axis_name)
    e_g = lax.psum(e_g, axis_name)
    l_g = lax.psum(l_g, axis_name)
    for ax in reduce_axes:
        # Sequence parallelism: each context shard computed PARTIAL param
        # grads over its local sequence; sum them. msums are already global
        # (loss_fn psums its sums over these axes before returning), so
        # they are NOT reduced again here.
        e_g = lax.psum(e_g, ax)
        l_g = lax.psum(l_g, ax)
        s_g = lax.psum(s_g, ax)
    s_g = jax.tree.map(lambda g: g[None], s_g)
    return msums, s_g, e_g, l_g


def stack_circular_stages(global_params: Any, n_stages: int) -> Any:
    """Re-stack [L, ...] global stage params (L = S·V) into the circular
    layout [S, V, ...] where slot [d, v] holds global stage v·S + d —
    shard the leading axis over `pipeline` and each device gets its V
    virtual stages."""

    def restack(p):
        L = p.shape[0]
        if L % n_stages:
            raise ValueError(
                f"global stages ({L}) must divide by pipeline size ({n_stages})"
            )
        v = L // n_stages
        # idx[d, v] = v*S + d; fancy-indexing with it yields [S, V, ...].
        idx = jnp.arange(L).reshape(v, n_stages).T
        return jnp.asarray(p)[idx]

    return jax.tree.map(restack, global_params)
