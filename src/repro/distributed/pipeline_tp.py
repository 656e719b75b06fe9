"""Pipeline parallelism with MANUAL tensor parallelism (the §Perf endpoint).

pipeline.py (GSPMD-auto TP inside the stage shard_map) still leaves XLA
guessing activation layouts across the fwd/bwd boundary: the dry-run showed
f32 cotangent all-gathers of the full q tensor per layer per tick (32 TB).
This variant removes every degree of freedom: the shard_map is MANUAL over
both mesh axes and all TP collectives are hand-placed —

  * layer fwd: local head-slice attention (group-major GQA means model-rank r
    owns query group r and computes ALL kv heads from replicated wk/wv — no
    kv resharding exists at all), one psum after wo and one after w2
    (textbook Megatron);
  * backward: `jax.vjp` of the manual stage — the only bwd collectives are
    the transposes of those psums;
  * stash: each model rank stores its 1/TP seq-slice in bf16 (2.1 GB not
    34 GB for llama3-405b) and `all_gather`s it back on the bwd tick;
  * embedding gather and the vocab-sharded softmax loss are hand-rolled
    masked-gather + psum / stop-gradient-logsumexp.

Expected collective budget per step (llama3-405b, 16 stages x 16 TP,
16 micros): ~2 psums x 2.1 GB x 8 layers x 31 ticks x (fwd+bwd) ~= 2 TB —
40x less than the ZeRO-3 baseline's 87 TB.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from repro import compat
from repro.compat import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.distributed.pipeline import PipeConfig, pad_layer_stack, plan  # noqa: F401
from repro.models import transformer as tfm
from repro.nn import layers as L
from repro.nn.chunked_attn import chunked_attention


# ---------------------------------------------------------------------------
# manual-TP building blocks (run inside a fully-manual shard_map)
# ---------------------------------------------------------------------------


def _embed_fwd(emb_loc, ids, dt, tp_axis):
    """Vocab-sharded embedding gather: masked local take + psum."""
    vsh = emb_loc.shape[0]
    rank = jax.lax.axis_index(tp_axis)
    loc = ids - rank * vsh
    inb = (loc >= 0) & (loc < vsh)
    rows = emb_loc[jnp.clip(loc, 0, vsh - 1)]
    rows = jnp.where(inb[..., None], rows, 0)
    return jax.lax.psum(rows, tp_axis).astype(dt)


def _layer_fwd(cfg, x, lp, positions, tp_axis):
    """One transformer layer, manual Megatron TP.

    lp holds LOCAL shards: wq (d, Hloc*dh), wk/wv full (d, Hkv*dh),
    wo (Hloc*dh, d), w1/w3 (d, ff_loc), w2 (ff_loc, d); norms replicated.
    Model-rank r owns query heads [r*Hloc, (r+1)*Hloc) = group-major groups.
    """
    b, s, d = x.shape
    dh = cfg.dh
    h_loc = lp["wq"].shape[-1] // dh

    hn = L.rms_norm(x, lp["attn_norm"])
    q = (hn @ lp["wq"]).reshape(b, s, h_loc, dh)
    k = (hn @ lp["wk"]).reshape(b, s, cfg.n_kv, dh)
    v = (hn @ lp["wv"]).reshape(b, s, cfg.n_kv, dh)
    q = L.rope(q, positions, cfg.rope_theta).transpose(0, 2, 1, 3)
    k = L.rope(k, positions, cfg.rope_theta).transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)
    if s >= 2048:
        attn = chunked_attention(q, k, v, causal=True,
                                 vary_axes=("data", tp_axis))
    else:
        from repro.kernels.ref import attention_ref

        attn = attention_ref(q, k, v, causal=True)
    attn = attn.transpose(0, 2, 1, 3).reshape(b, s, h_loc * dh)
    x = x + jax.lax.psum(attn @ lp["wo"], tp_axis)

    hn = L.rms_norm(x, lp["mlp_norm"])
    ff = jax.nn.silu(hn @ lp["w1"]) * (hn @ lp["w3"])
    x = x + jax.lax.psum(ff @ lp["w2"], tp_axis)
    return x


def _stage_fwd(cfg, slab, x, positions, tp_axis):
    def body(h, lp):
        return _layer_fwd(cfg, h, lp, positions, tp_axis), None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, slab)
    return x


def _head_loss(cfg, y, head_loc, fnorm, lbls, tp_axis):
    """Vocab-sharded cross entropy (stop-gradient logsumexp trick)."""
    vsh = head_loc.shape[-1]
    rank = jax.lax.axis_index(tp_axis)
    x = L.rms_norm(y, fnorm)
    logits = (x @ head_loc).astype(jnp.float32)          # (b, s, vsh)
    col = rank * vsh + jnp.arange(vsh)
    logits = jnp.where(col[None, None, :] < cfg.vocab, logits, -1e30)
    # stop_gradient BEFORE pmax: pmax has no differentiation rule, and the
    # logsumexp max-shift carries no gradient anyway
    m = jax.lax.pmax(
        jnp.max(jax.lax.stop_gradient(logits), axis=-1), tp_axis)  # (b, s)
    z = jax.lax.psum(jnp.sum(jnp.exp(logits - m[..., None]), axis=-1), tp_axis)
    lse = m + jnp.log(z)
    loc = lbls - rank * vsh
    inb = (loc >= 0) & (loc < vsh)
    gold = jnp.take_along_axis(
        logits, jnp.clip(loc, 0, vsh - 1)[..., None], axis=-1)[..., 0]
    gold = jax.lax.psum(jnp.where(inb, gold, 0.0), tp_axis)
    return jnp.mean(lse - gold)


# ---------------------------------------------------------------------------
# the pipelined step
# ---------------------------------------------------------------------------


def pipeline_tp_loss_and_grads(
    params: dict,
    tokens: jnp.ndarray,     # (M, mb, seq)
    labels: jnp.ndarray,
    cfg: tfm.TransformerConfig,
    pc: PipeConfig,
    mesh: Mesh,
    stage_axis: str = "data",
    tp_axis: str = "model",
):
    assert cfg.moe is None
    s_count, m_count = pc.n_stages, pc.n_micro
    tp = mesh.shape[tp_axis]
    ticks = m_count + s_count - 1
    dt = jnp.dtype(cfg.dtype)
    fwd_perm = [(i, (i + 1) % s_count) for i in range(s_count)]
    bwd_perm = [(i, (i - 1) % s_count) for i in range(s_count)]

    def per_stage(slab, embed, head, fnorm, toks, lbls):
        stage = jax.lax.axis_index(stage_axis)
        rank = jax.lax.axis_index(tp_axis)
        m, mb, seq = toks.shape
        d = cfg.d_model
        s_loc = seq // tp
        positions = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32), (mb, seq))
        is_first = stage == 0
        is_last = stage == s_count - 1

        stage_f = lambda sl, x: _stage_fwd(cfg, sl, x, positions, tp_axis)
        head_f = lambda y, hh, fn, lb: _head_loss(cfg, y, hh, fn, lb, tp_axis)

        # ---------------- forward fill-drain -----------------------------
        def fwd_tick(carry, t):
            act, stash = carry
            mi = t - stage
            active = (mi >= 0) & (mi < m_count)
            mi_c = jnp.clip(mi, 0, m_count - 1)
            x0 = _embed_fwd(embed, toks[mi_c], dt, tp_axis)
            x_in = jnp.where(is_first, x0, act)
            # stash this rank's seq slice only (bf16)
            my_slice = jax.lax.dynamic_slice_in_dim(
                x_in, rank * s_loc, s_loc, axis=1).astype(jnp.bfloat16)
            stash = jnp.where(
                active,
                jax.lax.dynamic_update_index_in_dim(stash, my_slice, mi_c, 0),
                stash,
            )
            y = stage_f(slab, x_in)
            y = jnp.where(active, y, jnp.zeros_like(y))
            return (jax.lax.ppermute(y, stage_axis, fwd_perm), stash), None

        # pvary: zero-init carries must carry the loop body's VMA type
        act0 = compat.pvary(jnp.zeros((mb, seq, d), dt), (stage_axis,))
        stash0 = compat.pvary(
            jnp.zeros((m_count, mb, s_loc, d), jnp.bfloat16),
            (stage_axis, tp_axis))
        (act, stash), _ = jax.lax.scan(
            fwd_tick, (act0, stash0), jnp.arange(ticks, dtype=jnp.int32))

        # ---------------- backward reversed fill-drain -------------------
        # zero-init carries must carry the body's VMA type: TP-sharded param
        # grads vary over (stage, tp); replicated-param grads (wk/wv/norms —
        # VMA auto-psums their cotangents over tp) vary over stage only
        both = (stage_axis, tp_axis)
        sonly = (stage_axis,)
        vary_of = {"attn_norm": sonly, "mlp_norm": sonly, "wk": sonly,
                   "wv": sonly}
        g_slab0 = {
            k: compat.pvary(jnp.zeros(p.shape, jnp.float32),
                             vary_of.get(k, both))
            for k, p in slab.items()
        }
        g_embed0 = compat.pvary(jnp.zeros(embed.shape, jnp.float32), both)
        # head/fnorm grads arrive stage-psum'd (stage-invariant): only TP
        # variance remains for the sharded head; fnorm is fully invariant
        g_head0 = compat.pvary(jnp.zeros(head.shape, jnp.float32), (tp_axis,))
        g_fnorm0 = jnp.zeros(fnorm.shape, jnp.float32)

        def stage_from_slice(sl, my_slice):
            # the all_gather lives INSIDE the vjp so its transpose
            # (reduce-scatter) correctly accumulates cross-TP-rank cotangent
            # contributions into the slice gradient
            x_in = jax.lax.all_gather(
                my_slice, tp_axis, axis=1, tiled=True).astype(dt)
            return stage_f(sl, x_in)

        def bwd_tick(carry, t):
            dacc, g_slab, g_embed, g_head, g_fnorm, loss_sum = carry
            mi = (m_count - 1) - t + (s_count - 1 - stage)
            active = (mi >= 0) & (mi < m_count)
            mi_c = jnp.clip(mi, 0, m_count - 1)
            lastg = (active & is_last).astype(jnp.float32)

            y, vjp_stage = jax.vjp(stage_from_slice, slab, stash[mi_c])
            # the head loss is masked INSIDE the differentiated fn: VMA
            # auto-psums head/fnorm cotangents across stages (they are
            # stage-invariant params), so non-last stages must contribute
            # exactly zero BEFORE that psum happens
            loss_mi, head_vjp = jax.vjp(
                lambda yy, hh, fn: head_f(yy, hh, fn, lbls[mi_c]) * lastg,
                y, head, fnorm)
            # cotangent into the head loss: the invariant loss takes the full
            # 1.0 (VMA's psum transposes re-type cotangents)
            dy_head, g_h_mi, g_f_mi = head_vjp(
                compat.pvary(jnp.float32(1.0), (stage_axis,)))
            # cotangent convention into vjp_stage: SUM-DECOMPOSED over TP
            # ranks (the all_gather transpose reduce-scatters, i.e. sums).
            # dy_head already is (each rank carries its vocab slice's term);
            # the ring-forwarded dacc is full-valued -> divide by tp
            dy = jnp.where(is_last, dy_head.astype(dt), dacc / tp)
            dy = jnp.where(active, dy, jnp.zeros_like(dy))
            g_slab_mi, d_slice = vjp_stage(dy)
            gate = active.astype(jnp.float32)
            g_slab = jax.tree.map(
                lambda a, b: a + gate * b.astype(jnp.float32), g_slab, g_slab_mi)
            # g_h/g_f arrive already stage-psum'd (only the last stage's gate
            # was nonzero) — plain accumulation, no further mask or psum
            g_head = g_head + g_h_mi.astype(jnp.float32)
            g_fnorm = g_fnorm + g_f_mi.astype(jnp.float32)
            loss_sum = loss_sum + loss_mi
            # full dx: each rank's slice grad is complete after the
            # reduce-scatter transpose; reassemble for the ring send
            dx = jax.lax.all_gather(
                d_slice, tp_axis, axis=1, tiled=True).astype(dt)
            # embedding grad (stage 0): vocab-sharded masked scatter
            vsh = embed.shape[0]
            ids = toks[mi_c].reshape(-1)
            loc = ids - rank * vsh
            inb = (loc >= 0) & (loc < vsh) & (active & is_first)
            dx_flat = jnp.where(inb[:, None], dx.reshape(-1, d), 0.0)
            g_embed = g_embed.at[jnp.clip(loc, 0, vsh - 1)].add(
                dx_flat.astype(jnp.float32))
            dx_send = jnp.where(active, dx, jnp.zeros_like(dx))
            dacc_next = jax.lax.ppermute(dx_send, stage_axis, bwd_perm)
            return (dacc_next, g_slab, g_embed, g_head, g_fnorm, loss_sum), None

        carry0 = (compat.pvary(jnp.zeros((mb, seq, d), dt), both),
                  g_slab0, g_embed0, g_head0,
                  g_fnorm0, compat.pvary(jnp.float32(0.0), sonly))
        (dacc, g_slab, g_embed, g_head, g_fnorm, loss_sum), _ = jax.lax.scan(
            bwd_tick, carry0, jnp.arange(ticks, dtype=jnp.int32))

        loss = jax.lax.psum(loss_sum, stage_axis) / m_count
        g_embed = jax.lax.psum(g_embed, stage_axis) / m_count
        g_head = g_head / m_count      # stage-psum'd in the vjp
        g_fnorm = g_fnorm / m_count
        g_slab = jax.tree.map(lambda g: g / m_count, g_slab)
        return loss, g_slab, g_embed, g_head, g_fnorm

    # local shard layouts: stack dim over stages; TP dims over 'model'
    slab_specs = {
        "attn_norm": P(stage_axis, None),
        "mlp_norm": P(stage_axis, None),
        "wq": P(stage_axis, None, tp_axis),
        "wk": P(stage_axis, None, None),
        "wv": P(stage_axis, None, None),
        "wo": P(stage_axis, tp_axis, None),
        "w1": P(stage_axis, None, tp_axis),
        "w3": P(stage_axis, None, tp_axis),
        "w2": P(stage_axis, tp_axis, None),
    }
    fn = shard_map(
        per_stage,
        mesh=mesh,
        in_specs=(slab_specs, P(tp_axis, None), P(None, tp_axis), P(),
                  P(), P()),
        out_specs=(P(), slab_specs, P(tp_axis, None), P(None, tp_axis), P()),
        axis_names={stage_axis, tp_axis},
        # VMA tracking ON: it inserts the cross-rank psums for cotangents of
        # replicated values (wk/wv grads, dy through the head, dx through the
        # residual stream) — with it off those grads come back wrong
        check_vma=True,
    )
    loss, g_layers, g_embed, g_head, g_fnorm = fn(
        params["layers"], params["embed"], params["lm_head"],
        params["final_norm"], tokens, labels,
    )
    return loss, {
        "layers": g_layers,
        "embed": g_embed,
        "lm_head": g_head,
        "final_norm": g_fnorm,
    }
