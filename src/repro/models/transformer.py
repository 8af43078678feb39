"""Composable decoder stack covering all 10 assigned architectures.

One scan over stacked per-layer params; layer heterogeneity (gemma3's
local/global pattern, xlstm's mLSTM/sLSTM alternation) enters as
per-layer flag arrays fed as scan xs (DESIGN.md §8.1).

Block types (``cfg.block_type``):
- ``attn``   — pre-norm attention (GQA or MLA) + pre-norm MLP (dense or MoE)
- ``hymba``  — parallel attention ∥ mamba heads, outputs fused as the mean
               of per-branch RMS-normed outputs (Hymba §2), then MLP
- ``xlstm``  — mLSTM or sLSTM core per layer flag, no separate MLP

Public API:
- ``init_transformer`` / ``transformer_specs`` — params + logical axes
- ``forward``        — full-sequence hidden states (+ MoE aux loss)
- ``loss_fn``        — seq-chunked softmax CE (never materializes (B,S,V))
- ``init_cache`` / ``prefill`` / ``decode_step`` — serving path
- ``layer_flags``    — per-layer pattern flags

``mesh`` is threaded through (None on CPU): when present and
``cfg.moe.impl == "capacity"``, the MoE runs expert-parallel inside
``shard_map`` over the ``model`` axis.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.common import activation, lecun_init, rms_norm, layer_norm, rope_table

__all__ = [
    "init_transformer", "transformer_specs", "layer_flags",
    "forward", "loss_fn", "output_head",
    "init_cache", "prefill", "decode_step",
]


# ---------------------------------------------------------------------------
# Flags / patterns
# ---------------------------------------------------------------------------


def layer_flags(cfg) -> dict[str, np.ndarray]:
    pat = (cfg.layer_pattern * cfg.n_layers)[: cfg.n_layers]
    if len(cfg.layer_pattern) == cfg.n_layers:
        pat = cfg.layer_pattern
    is_global = np.array([1.0 if c in "G" else 0.0 for c in pat], np.float32)
    is_mlstm = np.array([1.0 if c == "M" else 0.0 for c in pat], np.float32)
    return {"is_global": is_global, "is_mlstm": is_mlstm}


def _norm(p, cfg, x, name):
    if cfg.norm == "layernorm":
        return layer_norm(x, p[name + "_scale"], p[name + "_bias"], cfg.norm_eps)
    return rms_norm(x, p[name], cfg.norm_eps)


def _init_norm(cfg, name) -> dict:
    if cfg.norm == "layernorm":
        return {
            name + "_scale": jnp.ones((cfg.d_model,), jnp.float32),
            name + "_bias": jnp.zeros((cfg.d_model,), jnp.float32),
        }
    return {name: jnp.zeros((cfg.d_model,), jnp.float32)}


def _norm_specs(cfg, name) -> dict:
    if cfg.norm == "layernorm":
        return {name + "_scale": (None,), name + "_bias": (None,)}
    return {name: (None,)}


# ---------------------------------------------------------------------------
# Dense MLP
# ---------------------------------------------------------------------------


def _init_mlp(key, cfg) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 3)
    p = {
        "w_up": lecun_init(ks[0], (d, f), dt),
        "w_down": lecun_init(ks[1], (f, d), dt, fan_in=f),
    }
    if cfg.mlp_activation in ("swiglu", "geglu"):
        p["w_gate"] = lecun_init(ks[2], (d, f), dt)
    return p


def _mlp_specs(cfg) -> dict:
    s = {"w_up": ("embed", "ffn"), "w_down": ("ffn", "embed")}
    if cfg.mlp_activation in ("swiglu", "geglu"):
        s["w_gate"] = ("embed", "ffn")
    return s


def _mlp(p, cfg, x):
    gate = x @ p["w_gate"] if "w_gate" in p else None
    h = activation(cfg.mlp_activation, x @ p["w_up"], gate)
    return h @ p["w_down"]


# ---------------------------------------------------------------------------
# Layer init / specs
# ---------------------------------------------------------------------------


def _init_layer(key, cfg) -> dict:
    ks = jax.random.split(key, 4)
    if cfg.block_type == "xlstm":
        return {"xlstm": ssm_mod.init_xlstm(ks[0], cfg), **_init_norm(cfg, "norm1")}
    p = {**_init_norm(cfg, "norm1"), **_init_norm(cfg, "norm2")}
    p["attn"] = attn.init_mla(ks[0], cfg) if cfg.use_mla else attn.init_gqa(ks[0], cfg)
    if cfg.block_type == "hymba":
        p["ssm"] = ssm_mod.init_mamba(ks[1], cfg)
        p["attn_out_norm"] = jnp.zeros((cfg.d_model,), jnp.float32)
        p["ssm_out_norm"] = jnp.zeros((cfg.d_model,), jnp.float32)
    p["mlp"] = moe_mod.init_moe(ks[2], cfg) if cfg.moe else _init_mlp(ks[2], cfg)
    return p


def _layer_specs(cfg) -> dict:
    if cfg.block_type == "xlstm":
        return {"xlstm": ssm_mod.xlstm_specs(cfg), **_norm_specs(cfg, "norm1")}
    s = {**_norm_specs(cfg, "norm1"), **_norm_specs(cfg, "norm2")}
    s["attn"] = attn.mla_specs(cfg) if cfg.use_mla else attn.gqa_specs(cfg)
    if cfg.block_type == "hymba":
        s["ssm"] = ssm_mod.mamba_specs(cfg)
        s["attn_out_norm"] = (None,)
        s["ssm_out_norm"] = (None,)
    s["mlp"] = moe_mod.moe_specs(cfg) if cfg.moe else _mlp_specs(cfg)
    return s


# ---------------------------------------------------------------------------
# Model init / specs
# ---------------------------------------------------------------------------


def init_transformer(key, cfg) -> dict:
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.dtype)
    layer_keys = jax.random.split(ks[0], cfg.n_layers)
    layers = jax.vmap(lambda k: _init_layer(k, cfg))(layer_keys)
    p = {"layers": layers, **_init_norm(cfg, "final_norm")}
    if cfg.input_mode in ("tokens", "vlm"):
        p["embed"] = (
            jax.random.normal(ks[1], (cfg.vocab, cfg.d_model), jnp.float32) * 0.02
        ).astype(dt)
    else:  # frames arrive at d_model from the stub frontend
        p["frame_norm"] = jnp.zeros((cfg.d_model,), jnp.float32)
        p["embed"] = (
            jax.random.normal(ks[1], (cfg.vocab, cfg.d_model), jnp.float32) * 0.02
        ).astype(dt)  # output vocab table (EnCodec codes)
    if not cfg.tie_embeddings:
        p["head"] = lecun_init(ks[2], (cfg.d_model, cfg.vocab), dt)
    if cfg.mtp:
        p["mtp_proj"] = lecun_init(ks[3], (cfg.d_model, cfg.d_model), dt)
        p["mtp_norm"] = jnp.zeros((cfg.d_model,), jnp.float32)
    return p


def transformer_specs(cfg) -> dict:
    layers = jax.tree.map(
        lambda axes: ("layers",) + tuple(axes),
        _layer_specs(cfg),
        is_leaf=lambda x: isinstance(x, tuple),
    )
    s = {"layers": layers, **_norm_specs(cfg, "final_norm")}
    s["embed"] = ("vocab", "embed")
    if cfg.input_mode not in ("tokens", "vlm"):
        s["frame_norm"] = (None,)
    if not cfg.tie_embeddings:
        s["head"] = ("embed", "vocab")
    if cfg.mtp:
        s["mtp_proj"] = ("embed", "embed2")
        s["mtp_norm"] = (None,)
    return s


# ---------------------------------------------------------------------------
# Embedding of modal inputs
# ---------------------------------------------------------------------------


def embed_inputs(params, cfg, batch) -> tuple[jax.Array, jax.Array | None]:
    """batch → (x (B,S,d), loss_mask (B,S) or None).

    tokens: {"tokens": (B,S) int32}
    frames: {"frames": (B,S,d) bf16}             (audio stub frontend)
    vlm:    {"patches": (B,P,d) bf16, "tokens": (B,S−P) int32}
    """
    if cfg.input_mode == "tokens":
        x = jnp.take(params["embed"], batch["tokens"], axis=0)
        if cfg.tie_embeddings:
            x = x * jnp.sqrt(cfg.d_model).astype(x.dtype)
        return x, None
    if cfg.input_mode == "frames":
        x = rms_norm(batch["frames"].astype(jnp.dtype(cfg.dtype)), params["frame_norm"], cfg.norm_eps)
        return x, None
    if cfg.input_mode == "vlm":
        tok = jnp.take(params["embed"], batch["tokens"], axis=0)
        x = jnp.concatenate([batch["patches"].astype(tok.dtype), tok], axis=1)
        bsz, s = x.shape[0], x.shape[1]
        mask = jnp.concatenate(
            [jnp.zeros((bsz, cfg.n_patches)), jnp.ones((bsz, s - cfg.n_patches))], axis=1
        )
        return x, mask
    raise ValueError(cfg.input_mode)


def _rope_tables(cfg, seq_len, positions=None):
    """Two (S, rot/2) tables (local theta, global theta).  ``positions``
    (decode) selects single rows."""
    if cfg.use_mla:
        dim = cfg.qk_rope_head_dim
    else:
        hd = cfg.resolved_head_dim
        dim = int(hd * cfg.rope_fraction)
        dim -= dim % 2
    if dim == 0:
        dim = 2
    sin_l, cos_l = rope_table(seq_len, dim, cfg.rope_theta)
    if cfg.rope_theta_global:
        sin_g, cos_g = rope_table(seq_len, dim, cfg.rope_theta_global)
    else:
        sin_g, cos_g = sin_l, cos_l
    if positions is not None:
        def sel(t):
            return jax.lax.dynamic_slice_in_dim(t, positions, 1, axis=0)

        sin_l, cos_l, sin_g, cos_g = sel(sin_l), sel(cos_l), sel(sin_g), sel(cos_g)
    return (sin_l, cos_l), (sin_g, cos_g)


def _select_rope(tabs_l, tabs_g, is_global):
    sin = jnp.where(is_global > 0, tabs_g[0], tabs_l[0])
    cos = jnp.where(is_global > 0, tabs_g[1], tabs_l[1])
    return sin, cos


# ---------------------------------------------------------------------------
# MoE dispatch (impl × mesh)
# ---------------------------------------------------------------------------


def _run_moe(p_mlp, cfg, x, mesh):
    if cfg.moe.impl == "dense" or mesh is None:
        return moe_mod.moe_dense(p_mlp, cfg, x)
    all_axes = tuple(mesh.axis_names)
    n_dev = 1
    for a in all_axes:
        n_dev *= mesh.shape[a]
    tokens = x.shape[0] * x.shape[1]
    if tokens <= 8192 and cfg.moe.n_experts % n_dev == 0:
        # §Perf (decode iteration): full expert parallelism.  At decode the
        # baseline layout FSDP-gathers GBs of expert weights per layer for
        # a handful of tokens; instead keep ONE expert fully resident per
        # device, replicate the (tiny) token batch, psum the combine —
        # collective bytes drop from O(expert weights) to O(tokens·d).
        e_loc = cfg.moe.n_experts // n_dev
        pspec = jax.tree.map(lambda _: P(), p_mlp)
        pspec["w_gate"] = P(all_axes, None, None)
        pspec["w_up"] = P(all_axes, None, None)
        pspec["w_down"] = P(all_axes, None, None)
        xspec = P(*([None] * x.ndim))

        def ep_block(pl, xl):
            b, s, d = xl.shape
            idx = jnp.zeros((), jnp.int32)
            for a in all_axes:
                idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
            x2d = xl.reshape(-1, d)
            out2d, aux = moe_mod.moe_capacity(
                pl, cfg, x2d, expert_offset=idx * e_loc, n_local_experts=e_loc,
                include_shared=False,
            )
            out2d = jax.lax.psum(out2d, all_axes)
            if cfg.moe.n_shared:
                out2d = out2d + moe_mod._shared_expert(pl, cfg, x2d)
            return out2d.reshape(b, s, d), aux

        return jax.shard_map(
            ep_block, mesh=mesh, in_specs=(pspec, xspec), out_specs=(xspec, P()),
            check_vma=False,
        )(p_mlp, x)
    if (
        tokens <= 8192
        and cfg.moe.n_experts % mesh.shape["model"] == 0
        and cfg.moe.d_expert % (n_dev // mesh.shape["model"]) == 0
    ):
        # §Perf (decode iteration, few-expert MoE e.g. dbrx): experts over
        # `model`, expert-FFN columns over the data axes.  The gated
        # activation is elementwise over ff columns, so column-parallel
        # expert compute is exact; the combine psum over all axes sums
        # disjoint expert contributions (model) and ff partials (data) —
        # again no per-layer weight gather at decode.
        dp_axes_all = tuple(a for a in all_axes if a != "model")
        e_loc = cfg.moe.n_experts // mesh.shape["model"]
        pspec = jax.tree.map(lambda _: P(), p_mlp)
        pspec["w_gate"] = P("model", None, dp_axes_all)
        pspec["w_up"] = P("model", None, dp_axes_all)
        pspec["w_down"] = P("model", dp_axes_all, None)
        xspec = P(*([None] * x.ndim))

        def tp_block(pl, xl):
            b, s, d = xl.shape
            idx = jax.lax.axis_index("model")
            x2d = xl.reshape(-1, d)
            out2d, aux = moe_mod.moe_capacity(
                pl, cfg, x2d, expert_offset=idx * e_loc, n_local_experts=e_loc,
                include_shared=False,
            )
            out2d = jax.lax.psum(out2d, all_axes)
            if cfg.moe.n_shared:
                out2d = out2d + moe_mod._shared_expert(pl, cfg, x2d)
            return out2d.reshape(b, s, d), aux

        return jax.shard_map(
            tp_block, mesh=mesh, in_specs=(pspec, xspec), out_specs=(xspec, P()),
            check_vma=False,
        )(p_mlp, x)
    if cfg.moe.n_experts % mesh.shape["model"] != 0:
        # cannot expert-shard evenly — replicated capacity path
        out, aux = moe_mod.moe_capacity(p_mlp, cfg, x.reshape(-1, x.shape[-1]))
        return out.reshape(x.shape), aux
    dp_axes = tuple(n for n in mesh.axis_names if n != "model")
    dp_total = 1
    for a in dp_axes:
        dp_total *= mesh.shape[a]
    if x.shape[0] % dp_total != 0:
        dp_axes = ()  # batch too small (decode long_500k): replicate tokens
    xspec = P(dp_axes if dp_axes else None, None, None)
    pspec = jax.tree.map(lambda _: P(), p_mlp)
    pspec["w_gate"] = P("model", None, None)
    pspec["w_up"] = P("model", None, None)
    pspec["w_down"] = P("model", None, None)

    def block(pl, xl):
        out, aux = moe_mod.moe_capacity_sharded(pl, cfg, xl, mesh_axis="model")
        if dp_axes:
            aux = jax.lax.pmean(aux, dp_axes)
        return out, aux

    # NOTE (§Perf deepseek iteration 2, refuted hypothesis): the shared
    # expert is computed INSIDE the shard_map, replicated over `model`.
    # Tensor-parallelizing it under GSPMD-auto cut the compute term −36%
    # but the per-token down-proj all-reduce raised the collective term
    # +37% — a net wall-time regression (≈87 ms redundant compute vs
    # ≈118 ms TP+all-reduce per layer on v5e napkin numbers).  Redundant
    # compute beats communication for this thin (d_ff=2048) layer.
    return jax.shard_map(
        block, mesh=mesh, in_specs=(pspec, xspec), out_specs=(xspec, P()),
        check_vma=False,
    )(p_mlp, x)


# ---------------------------------------------------------------------------
# Layer apply (full sequence)
# ---------------------------------------------------------------------------


def _act_constraint(cfg, x, mesh):
    """Optional explicit activation sharding (§Perf iteration 2): pins the
    residual stream to batch-sharded layout so GSPMD does not introduce
    per-op resharding churn (observed as 'involuntary full
    rematerialization' all-gathers under the fsdp policy)."""
    if mesh is None or not cfg.act_shard:
        return x
    from jax.sharding import NamedSharding

    if cfg.act_shard == "dp_all":
        axes = tuple(mesh.axis_names)
    else:  # dp_data
        axes = tuple(a for a in mesh.axis_names if a != "model")
    if x.shape[0] % int(np.prod([mesh.shape[a] for a in axes])) != 0:
        return x
    spec = P(axes, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _apply_layer_seq(pl, cfg, x, flags, tabs_l, tabs_g, mesh):
    """One layer, full sequence.  Returns (x_out, aux, cache_entry)."""
    x = _act_constraint(cfg, x, mesh)
    is_global = flags["is_global"]
    sin, cos = _select_rope(tabs_l, tabs_g, is_global)
    aux = jnp.zeros((), jnp.float32)

    if cfg.block_type == "xlstm":
        h = _norm(pl, cfg, x, "norm1")
        out_m, st_m = ssm_mod.mlstm_seq(pl["xlstm"], cfg, h)
        out_s, st_s = ssm_mod.slstm_seq(pl["xlstm"], cfg, h)
        is_m = flags["is_mlstm"]
        out = jnp.where(is_m > 0, out_m, out_s)
        x = x + out
        cache = {"mlstm": st_m, "slstm": st_s}
        return x, aux, cache

    h = _norm(pl, cfg, x, "norm1")
    if cfg.use_mla:
        a_out, kv = attn.mla_attention(pl["attn"], cfg, h, sin, cos, is_global)
        cache = {"latent": kv[0], "k_rope": kv[1]}
    else:
        a_out, kv = attn.gqa_attention(pl["attn"], cfg, h, sin, cos, is_global)
        cache = {"k": kv[0], "v": kv[1]}

    if cfg.block_type == "hymba":
        s_out, (h_fin, conv_tail) = ssm_mod.mamba_seq(pl["ssm"], cfg, h)
        a_out = 0.5 * (
            rms_norm(a_out, pl["attn_out_norm"], cfg.norm_eps)
            + rms_norm(s_out, pl["ssm_out_norm"], cfg.norm_eps)
        )
        cache.update({"ssm_h": h_fin, "conv": conv_tail})
    x = x + a_out

    h2 = _norm(pl, cfg, x, "norm2")
    if cfg.moe:
        m_out, aux = _run_moe(pl["mlp"], cfg, h2, mesh)
    else:
        m_out = _mlp(pl["mlp"], cfg, h2)
    x = x + m_out
    return x, aux, cache


def forward(params, cfg, batch, mesh=None, collect_cache: bool = False):
    """Full-sequence forward.  Returns (hidden (B,S,d), loss_mask, aux,
    caches-or-None)."""
    x, loss_mask = embed_inputs(params, cfg, batch)
    s = x.shape[1]
    tabs_l, tabs_g = _rope_tables(cfg, s)
    flags = layer_flags(cfg)
    flags_j = {k: jnp.asarray(v) for k, v in flags.items()}

    def body(carry, xs):
        x, aux_acc = carry
        pl, fl = xs
        x, aux, cache = _apply_layer_seq(pl, cfg, x, fl, tabs_l, tabs_g, mesh)
        return (x, aux_acc + aux), (cache if collect_cache else 0)

    body_fn = jax.checkpoint(body) if cfg.remat else body
    (x, aux), caches = jax.lax.scan(
        body_fn, (x, jnp.zeros((), jnp.float32)), (params["layers"], flags_j),
        unroll=cfg.scan_unroll,
    )
    x = _norm(params, cfg, x, "final_norm")
    return x, loss_mask, aux / cfg.n_layers, (caches if collect_cache else None)


# ---------------------------------------------------------------------------
# Loss (seq-chunked CE) and logits
# ---------------------------------------------------------------------------


def output_head(params, cfg):
    """The (d_model, vocab) output projection — tied embedding transpose
    or the separate head.  Public so downstream losses (e.g. the
    federated LM task) share one untying rule with ``loss_fn``."""
    return params["embed"].T if cfg.tie_embeddings else params["head"]


def _logits(params, cfg, h):
    return h @ output_head(params, cfg)


def loss_fn(params, cfg, batch, mesh=None):
    """Mean next-token CE, computed over sequence chunks so the full
    (B,S,V) logits tensor never exists.  Returns (loss, metrics)."""
    h, loss_mask, aux, _ = forward(params, cfg, batch, mesh)
    labels = batch["labels"]
    b, s, _ = h.shape
    c = min(cfg.loss_chunk, s)
    nc = s // c
    assert nc * c == s

    mask = loss_mask if loss_mask is not None else jnp.ones((b, s), jnp.float32)

    def chunk_ce(hc, yc, mc):
        logits = _logits(params, cfg, hc).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, yc[..., None].astype(jnp.int32), axis=-1)[..., 0]
        return jnp.sum((logz - gold) * mc), jnp.sum(mc)

    def body(carry, i):
        tot, cnt = carry
        hc = jax.lax.dynamic_slice_in_dim(h, i * c, c, axis=1)
        yc = jax.lax.dynamic_slice_in_dim(labels, i * c, c, axis=1)
        mc = jax.lax.dynamic_slice_in_dim(mask, i * c, c, axis=1)
        t, n = chunk_ce(hc, yc, mc)
        return (tot + t, cnt + n), None

    (tot, cnt), _ = jax.lax.scan(body, (jnp.zeros(()), jnp.zeros(())), jnp.arange(nc))
    ce = tot / jnp.maximum(cnt, 1.0)
    loss = ce
    metrics = {"ce": ce, "aux": aux}
    if cfg.moe:
        loss = loss + cfg.moe.router_aux_weight * aux
    if cfg.mtp:
        # Predict t+2 from a light projection of the trunk (DESIGN.md §5).
        h_mtp = rms_norm(h @ params["mtp_proj"], params["mtp_norm"], cfg.norm_eps)
        y2 = jnp.roll(labels, -1, axis=1)
        m2 = mask * (jnp.arange(s) < s - 1)[None, :]

        def body2(carry, i):
            tot, cnt = carry
            hc = jax.lax.dynamic_slice_in_dim(h_mtp, i * c, c, axis=1)
            yc = jax.lax.dynamic_slice_in_dim(y2, i * c, c, axis=1)
            mc = jax.lax.dynamic_slice_in_dim(m2, i * c, c, axis=1)
            t, n = chunk_ce(hc, yc, mc)
            return (tot + t, cnt + n), None

        (tot2, cnt2), _ = jax.lax.scan(body2, (jnp.zeros(()), jnp.zeros(())), jnp.arange(nc))
        mtp_ce = tot2 / jnp.maximum(cnt2, 1.0)
        loss = loss + cfg.mtp_weight * mtp_ce
        metrics["mtp_ce"] = mtp_ce
    return loss, metrics


# ---------------------------------------------------------------------------
# Serving: cache init, prefill, decode
# ---------------------------------------------------------------------------


def init_cache(cfg, batch_size: int, max_len: int) -> dict:
    """Stacked (L-leading) decode cache for the arch's block type."""
    L = cfg.n_layers
    d = cfg.d_model
    dt = jnp.dtype(cfg.dtype)
    if cfg.block_type == "xlstm":
        hh = cfg.ssm.n_heads
        hd = d // hh
        return {
            "mlstm": (
                jnp.zeros((L, batch_size, hh, hd, hd), jnp.float32),
                jnp.zeros((L, batch_size, hh, hd), jnp.float32),
                jnp.full((L, batch_size, hh), -1e30, jnp.float32),
            ),
            "slstm": (
                jnp.zeros((L, batch_size, hh, hd), jnp.float32),
                jnp.zeros((L, batch_size, hh, hd), jnp.float32),
                jnp.full((L, batch_size, hh), -1e30, jnp.float32),
            ),
        }
    cache: dict = {}
    if cfg.use_mla:
        cache["latent"] = jnp.zeros((L, batch_size, max_len, cfg.kv_lora_rank), dt)
        cache["k_rope"] = jnp.zeros((L, batch_size, max_len, cfg.qk_rope_head_dim), dt)
    else:
        kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
        cache["k"] = jnp.zeros((L, batch_size, max_len, kv, hd), dt)
        cache["v"] = jnp.zeros((L, batch_size, max_len, kv, hd), dt)
    if cfg.block_type == "hymba":
        n = cfg.ssm.d_state
        cache["ssm_h"] = jnp.zeros((L, batch_size, d, n), jnp.float32)
        cache["conv"] = jnp.zeros((L, batch_size, cfg.ssm.conv_kernel - 1, d), jnp.float32)
    return cache


def cache_specs(cfg) -> dict:
    """Logical axes for the cache pytree (mirrors init_cache)."""
    if cfg.block_type == "xlstm":
        # lists (not tuples) so tree flattening stops at the axis tuples
        return {
            "mlstm": [
                ("layers", "batch", None, None, None),
                ("layers", "batch", None, None),
                ("layers", "batch", None),
            ],
            "slstm": [
                ("layers", "batch", None, None),
                ("layers", "batch", None, None),
                ("layers", "batch", None),
            ],
        }
    s: dict = {}
    if cfg.use_mla:
        s["latent"] = ("layers", "batch", "seq", None)
        s["k_rope"] = ("layers", "batch", "seq", None)
    else:
        s["k"] = ("layers", "batch", "seq", "kv_heads", None)
        s["v"] = ("layers", "batch", "seq", "kv_heads", None)
    if cfg.block_type == "hymba":
        s["ssm_h"] = ("layers", "batch", None, None)
        s["conv"] = ("layers", "batch", None, None)
    return s


def _cache_constraint(cache, mesh):
    """Pin decode-cache leaves to their storage layout (batch over data
    axes when divisible, else seq over data axes, rest replicated) so the
    while-loop carry is not resharded by GSPMD — without this, dbrx-style
    decode gathers the full per-layer KV cache every step (§Perf decode
    iteration)."""
    if mesh is None:
        return cache
    from jax.sharding import NamedSharding

    dp = tuple(a for a in mesh.axis_names if a != "model")
    dp_total = 1
    for a in dp:
        dp_total *= mesh.shape[a]

    def one(leaf):
        if leaf.ndim < 2:
            return leaf
        if leaf.shape[0] % dp_total == 0 and leaf.shape[0] > 1:
            spec = P(dp, *([None] * (leaf.ndim - 1)))
        elif leaf.ndim >= 2 and leaf.shape[1] % dp_total == 0 and leaf.shape[1] > 1:
            spec = P(None, dp, *([None] * (leaf.ndim - 2)))
        else:
            return leaf
        return jax.lax.with_sharding_constraint(leaf, NamedSharding(mesh, spec))

    return jax.tree.map(one, cache)


def _apply_layer_decode(pl, cfg, x, flags, tabs_l, tabs_g, cache_l, pos, mesh):
    cache_l = _cache_constraint(cache_l, mesh)
    is_global = flags["is_global"]
    sin, cos = _select_rope(tabs_l, tabs_g, is_global)

    if cfg.block_type == "xlstm":
        h = _norm(pl, cfg, x, "norm1")
        out_m, st_m = ssm_mod.mlstm_decode(pl["xlstm"], cfg, h, cache_l["mlstm"])
        out_s, st_s = ssm_mod.slstm_decode(pl["xlstm"], cfg, h, cache_l["slstm"])
        is_m = flags["is_mlstm"]
        out = jnp.where(is_m > 0, out_m, out_s)
        # only the active branch's state advances
        st_m = jax.tree.map(lambda new, old: jnp.where(is_m > 0, new, old), st_m, cache_l["mlstm"])
        st_s = jax.tree.map(lambda new, old: jnp.where(is_m > 0, old, new), st_s, cache_l["slstm"])
        return x + out, {"mlstm": st_m, "slstm": st_s}

    h = _norm(pl, cfg, x, "norm1")
    if cfg.use_mla:
        a_out, (lat, kr) = attn.mla_decode(
            pl["attn"], cfg, h, sin, cos, (cache_l["latent"], cache_l["k_rope"]), pos, is_global
        )
        new_cache = {"latent": lat, "k_rope": kr}
    else:
        a_out, (kc, vc) = attn.gqa_decode(
            pl["attn"], cfg, h, sin, cos, (cache_l["k"], cache_l["v"]), pos, is_global
        )
        new_cache = {"k": kc, "v": vc}

    if cfg.block_type == "hymba":
        s_out, (h_new, conv_new) = ssm_mod.mamba_decode(
            pl["ssm"], cfg, h, cache_l["ssm_h"], cache_l["conv"]
        )
        a_out = 0.5 * (
            rms_norm(a_out, pl["attn_out_norm"], cfg.norm_eps)
            + rms_norm(s_out, pl["ssm_out_norm"], cfg.norm_eps)
        )
        new_cache.update({"ssm_h": h_new, "conv": conv_new})
    x = x + a_out

    h2 = _norm(pl, cfg, x, "norm2")
    if cfg.moe:
        m_out, _ = _run_moe(pl["mlp"], cfg, h2, mesh)
    else:
        m_out = _mlp(pl["mlp"], cfg, h2)
    return x + m_out, new_cache


def decode_step(params, cfg, batch, cache, pos, mesh=None):
    """One-token decode.  batch: {"token": (B,1)} or {"frame": (B,1,d)};
    ``pos``: scalar int32 current position.  Returns (logits (B,V), cache)."""
    if cfg.input_mode == "tokens" or (cfg.input_mode == "vlm" and "token" in batch):
        x = jnp.take(params["embed"], batch["token"], axis=0)
        if cfg.tie_embeddings:
            x = x * jnp.sqrt(cfg.d_model).astype(x.dtype)
    else:
        x = rms_norm(
            batch["frame"].astype(jnp.dtype(cfg.dtype)), params["frame_norm"], cfg.norm_eps
        )
    # max_len known from cache; rope rows selected at pos
    if cfg.block_type == "xlstm":
        max_len = 1
    elif cfg.use_mla:
        max_len = cache["latent"].shape[2]
    else:
        max_len = cache["k"].shape[2]
    tabs_l, tabs_g = _rope_tables(cfg, max(max_len, 1), positions=pos)
    flags = {k: jnp.asarray(v) for k, v in layer_flags(cfg).items()}

    def body(x, xs):
        pl, fl, cl = xs
        x, new_cache = _apply_layer_decode(pl, cfg, x, fl, tabs_l, tabs_g, cl, pos, mesh)
        return x, _cache_constraint(new_cache, mesh)

    body_fn = jax.checkpoint(body) if cfg.remat else body
    x, new_cache = jax.lax.scan(
        body_fn, x, (params["layers"], flags, cache), unroll=cfg.scan_unroll
    )
    x = _norm(params, cfg, x, "final_norm")
    logits = _logits(params, cfg, x[:, 0])
    return logits, new_cache


def prefill(params, cfg, batch, max_len: int, mesh=None):
    """Prefill: run the prompt, return (last-position logits, cache padded
    to ``max_len``)."""
    h, _, _, caches = forward(params, cfg, batch, mesh, collect_cache=True)
    b, s, _ = h.shape
    logits = _logits(params, cfg, h[:, -1])
    out = init_cache(cfg, b, max_len)
    if cfg.block_type == "xlstm":
        # caches collected per layer: {"mlstm": (C,n,m), "slstm": ...} stacked on L
        flags = layer_flags(cfg)
        is_m = jnp.asarray(flags["is_mlstm"])

        def sel(new, zero, flag_nd):
            shape = (cfg.n_layers,) + (1,) * (new.ndim - 1)
            return jnp.where(is_m.reshape(shape) > 0 if flag_nd else is_m.reshape(shape) <= 0, new, zero)

        ml = jax.tree.map(lambda n_, z: sel(n_, z, True), caches["mlstm"], out["mlstm"])
        sl = jax.tree.map(lambda n_, z: sel(n_, z, False), caches["slstm"], out["slstm"])
        return logits, {"mlstm": ml, "slstm": sl}
    # sequence caches: place the s prefill entries at [0, s)
    for k in ("latent", "k_rope", "k", "v"):
        if k in out:
            out[k] = jax.lax.dynamic_update_slice_in_dim(
                out[k], caches[k].astype(out[k].dtype), 0, axis=2
            )
    if cfg.block_type == "hymba":
        out["ssm_h"] = caches["ssm_h"]
        out["conv"] = caches["conv"]
    return logits, out
