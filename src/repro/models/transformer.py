"""Decoder-only LM covering dense / moe / ssm / hybrid / vlm families.

Layers are grouped into *pattern units* (``cfg.block_pattern``) that repeat
``cfg.num_pattern_units`` times; unit params are stacked on a leading axis and
the forward pass is ``lax.scan`` over units (HLO size stays flat in depth).
Depth remainders (e.g. recurrentgemma's trailing 2 blocks) are unrolled.

Three entry points per model:
  * ``loss``        — training forward + mean token CE (+ MoE aux)
  * ``prefill``     — full-sequence forward that also fills decode caches
  * ``decode_step`` — one-token step against the caches
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import layers as L

Params = Dict[str, Any]


def constrain_acts(x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Optional residual-stream sharding constraint (cfg.act_pspec), a §Perf
    knob: pins the layout GSPMD must keep between layers instead of letting
    it re-shard (which showed up as per-layer activation all-gathers in the
    baseline HLO).  A no-op when no mesh is set in context (``jax.set_mesh``);
    under one, a constraint the mesh cannot satisfy raises."""
    if cfg.act_pspec is None or jax.sharding.get_abstract_mesh().empty:
        return x
    from jax.sharding import PartitionSpec as P
    return jax.lax.with_sharding_constraint(x, P(*cfg.act_pspec))


# --------------------------------------------------------------------------
# block init / apply
# --------------------------------------------------------------------------
def block_init(key, kind: str, cfg: ModelConfig, cross: bool = False) -> Params:
    ks = jax.random.split(key, 8)
    p: Params = {"ln1": L.norm_init(cfg.d_model, cfg)}
    if kind in ("global", "local"):
        p["attn"] = L.attention_init(ks[0], cfg)
    elif kind == "ssd":
        p["ssd"] = L.ssd_init(ks[0], cfg)
    elif kind == "rglru":
        p["rglru"] = L.rglru_init(ks[0], cfg)
    else:
        raise ValueError(kind)
    if cross:
        p["lnx"] = L.norm_init(cfg.d_model, cfg)
        p["xattn"] = L.attention_init(ks[1], cfg)
    has_mlp = cfg.mlp_variant != "none" and cfg.d_ff > 0 and kind != "ssd"
    if has_mlp:
        p["ln2"] = L.norm_init(cfg.d_model, cfg)
        if cfg.num_experts:
            p["moe"] = L.moe_init(ks[2], cfg)
        else:
            p["mlp"] = L.mlp_init(ks[2], cfg)
    return p


def _mixer_window(kind: str, cfg: ModelConfig) -> Optional[int]:
    return cfg.sliding_window if kind == "local" else None


def block_apply(p: Params, x: jax.Array, kind: str, cfg: ModelConfig, *,
                positions: jax.Array,
                enc_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
                self_mask: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence block. Returns (x, moe_aux)."""
    aux = jnp.zeros((), jnp.float32)
    h = L.apply_norm(p["ln1"], x, cfg)
    if kind in ("global", "local"):
        h = L.attention_apply(p["attn"], h, cfg, positions=positions,
                              window=_mixer_window(kind, cfg), mask=self_mask)
    elif kind == "ssd":
        h = L.ssd_apply(p["ssd"], h, cfg)
    elif kind == "rglru":
        h = L.rglru_apply(p["rglru"], h, cfg)
    x = x + h
    if "xattn" in p:
        assert enc_kv is not None
        h = L.apply_norm(p["lnx"], x, cfg)
        sq, sk = h.shape[-2], enc_kv[0].shape[-3]
        full = jnp.ones((sq, sk), bool)
        h = L.attention_apply(p["xattn"], h, cfg, positions=positions,
                              kv=enc_kv, mask=full, use_rope=False)
        x = x + h
    if "ln2" in p:
        h = L.apply_norm(p["ln2"], x, cfg)
        if "moe" in p:
            h, aux = L.moe_apply(p["moe"], h, cfg)
        else:
            h = L.mlp_apply(p["mlp"], h, cfg)
        x = x + h
    return x, aux


# ---- prefill: same forward but emits decode caches -------------------------
def block_prefill(p: Params, x: jax.Array, kind: str, cfg: ModelConfig, *,
                  positions: jax.Array, max_len: int,
                  enc_kv=None) -> Tuple[jax.Array, Params]:
    """Returns (x_out, cache) where cache layout matches block_decode."""
    b, s, _ = x.shape
    h = L.apply_norm(p["ln1"], x, cfg)
    cache: Params = {}
    if kind in ("global", "local"):
        k, v = L.attention_kv(p["attn"], h, cfg, positions=positions)
        if kind == "global":
            pad = max_len - s
            kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
            cache = {"k": kc, "v": vc}
        else:
            w = cfg.sliding_window
            # slot j holds the last prompt position p with p % w == j
            idx = np.array([s - 1 - ((s - 1 - j) % w) for j in range(w)])
            valid = idx >= 0
            idx_c = np.where(valid, idx, 0)
            kc = jnp.where(valid[None, :, None, None], k[:, idx_c], 0)
            vc = jnp.where(valid[None, :, None, None], v[:, idx_c], 0)
            slot_pos = jnp.asarray(np.where(valid, idx, -1), jnp.int32)
            cache = {"k": kc, "v": vc, "slot_pos": slot_pos}
    if kind in ("global", "local"):
        h = L.attention_apply(p["attn"], h, cfg, positions=positions,
                              window=_mixer_window(kind, cfg))
    elif kind == "ssd":
        z, xbc, dt, di, ns, nh = L._ssd_split(p["ssd"], h, cfg)
        xbc_conv = jax.nn.silu(L.conv1d_apply(p["ssd"]["conv"], xbc))
        xs, B, C = jnp.split(xbc_conv, [di, di + ns], axis=-1)
        dtp = jax.nn.softplus(dt.astype(jnp.float32) + p["ssd"]["dt_bias"])
        A = -jnp.exp(p["ssd"]["A_log"])
        ph = cfg.ssm_head_dim
        xh = xs.reshape(xs.shape[:-1] + (nh, ph))
        pad = (-s) % cfg.ssm_chunk
        if pad:
            xh_p = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
            dt_p = jnp.pad(dtp, ((0, 0), (0, pad), (0, 0)))
            B_p = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
            C_p = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
        else:
            xh_p, dt_p, B_p, C_p = xh, dtp, B, C
        y, state = L.ssd_scan_ref(xh_p, dt_p, A, B_p, C_p, cfg.ssm_chunk)
        y = y[:, :s] + xh * p["ssd"]["D"][:, None].astype(h.dtype)
        y = y.reshape(xs.shape)
        y = y * jax.nn.silu(z)
        ms = (y.astype(jnp.float32) ** 2).mean(-1, keepdims=True)
        y = (y.astype(jnp.float32) * jax.lax.rsqrt(ms + 1e-6)).astype(h.dtype) \
            * p["ssd"]["out_norm"]["scale"].astype(h.dtype)
        wdt = cfg.ssm_conv_width - 1
        conv_buf = xbc[:, -wdt:] if s >= wdt else jnp.pad(
            xbc, ((0, 0), (wdt - s, 0), (0, 0)))
        cache = {"ssm": state, "conv": conv_buf}
        h = y @ p["ssd"]["out_proj"].astype(h.dtype)
    elif kind == "rglru":
        xs = h @ p["rglru"]["in_x"].astype(h.dtype)
        gate = jax.nn.gelu(h @ p["rglru"]["in_gate"].astype(h.dtype))
        xs_pre = xs
        xs = L.conv1d_apply(p["rglru"]["conv"], xs)
        ys, h_final = L.rglru_core(p["rglru"], xs)
        wdt = cfg.conv1d_width - 1
        conv_buf = xs_pre[:, -wdt:] if s >= wdt else jnp.pad(
            xs_pre, ((0, 0), (wdt - s, 0), (0, 0)))
        cache = {"h": h_final, "conv": conv_buf}
        h = (ys * gate) @ p["rglru"]["out"].astype(h.dtype)
    x = x + h
    if "xattn" in p:
        hx = L.apply_norm(p["lnx"], x, cfg)
        sq, sk = hx.shape[-2], enc_kv[0].shape[-3]
        hx = L.attention_apply(p["xattn"], hx, cfg, positions=positions,
                               kv=enc_kv, mask=jnp.ones((sq, sk), bool),
                               use_rope=False)
        x = x + hx
    if "ln2" in p:
        h = L.apply_norm(p["ln2"], x, cfg)
        h = L.moe_apply(p["moe"], h, cfg)[0] if "moe" in p else \
            L.mlp_apply(p["mlp"], h, cfg)
        x = x + h
    return x, cache


def block_decode(p: Params, x: jax.Array, kind: str, cfg: ModelConfig, *,
                 cache: Params, pos: jax.Array,
                 enc_kv=None) -> Tuple[jax.Array, Params]:
    """One-token step. x: (B,1,D); pos: scalar int32 (position being written)."""
    b = x.shape[0]
    h = L.apply_norm(p["ln1"], x, cfg)
    if kind in ("global", "local"):
        k, v = L.attention_kv(p["attn"], h, cfg,
                              positions=jnp.full((b, 1), pos, jnp.int32))
        if kind == "global":
            kc = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                              (0, pos, 0, 0))
            vc = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                              (0, pos, 0, 0))
            cache = {"k": kc, "v": vc}
            smax = kc.shape[1]
            cpos = jnp.arange(smax, dtype=jnp.int32)
            cache_positions = jnp.where(cpos <= pos, cpos, -1)
        else:
            w = cfg.sliding_window
            slot = pos % w
            kc = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                              (0, slot, 0, 0))
            vc = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                              (0, slot, 0, 0))
            slot_pos = jax.lax.dynamic_update_slice(
                cache["slot_pos"], pos[None].astype(jnp.int32), (slot,))
            cache = {"k": kc, "v": vc, "slot_pos": slot_pos}
            cache_positions = slot_pos
        h = L.attention_decode(
            p["attn"], h, cfg, k_cache=cache["k"], v_cache=cache["v"],
            cache_positions=jnp.broadcast_to(cache_positions, (b,) + cache_positions.shape),
            position=jnp.full((b,), pos, jnp.int32))
    elif kind == "ssd":
        h, cache = L.ssd_decode(p["ssd"], h, cfg, cache)
    elif kind == "rglru":
        h, cache = L.rglru_decode(p["rglru"], h, cfg, cache)
    x = x + h
    if "xattn" in p:
        hx = L.apply_norm(p["lnx"], x, cfg)
        sk = enc_kv[0].shape[-3]
        hx = L.attention_apply(p["xattn"], hx, cfg,
                               positions=jnp.full((b, 1), pos, jnp.int32),
                               kv=enc_kv, mask=jnp.ones((1, sk), bool),
                               use_rope=False)
        x = x + hx
    if "ln2" in p:
        h = L.apply_norm(p["ln2"], x, cfg)
        h = L.moe_apply_dense(p["moe"], h, cfg) if "moe" in p else \
            L.mlp_apply(p["mlp"], h, cfg)
        x = x + h
    return x, cache


def block_cache_init(kind: str, cfg: ModelConfig, batch: int, max_len: int,
                     dtype) -> Params:
    if kind == "global":
        shape = (batch, max_len, cfg.num_kv_heads, cfg.d_head)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if kind == "local":
        w = cfg.sliding_window
        shape = (batch, w, cfg.num_kv_heads, cfg.d_head)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
                "slot_pos": jnp.full((w,), -1, jnp.int32)}
    if kind == "ssd":
        return L.ssd_init_state(cfg, batch, dtype)
    if kind == "rglru":
        return L.rglru_init_state(cfg, batch, dtype)
    raise ValueError(kind)


# --------------------------------------------------------------------------
# the decoder-only LM
# --------------------------------------------------------------------------
class DecoderLM:
    """Unified decoder-only LM. Stateless: params/caches are explicit."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ---- init ----------------------------------------------------------
    def init(self, key) -> Params:
        cfg = self.cfg
        ks = jax.random.split(key, 4 + len(cfg.pattern_remainder))
        emb = (jax.random.normal(ks[0], (cfg.vocab_size, cfg.d_model)) * 0.02
               ).astype(cfg.param_dtype)
        params: Params = {"embed": emb, "final_norm": L.norm_init(cfg.d_model, cfg)}
        if not cfg.tie_embeddings:
            params["lm_head"] = L.dense_init(ks[1], cfg.d_model, cfg.vocab_size,
                                             cfg.param_dtype)
        n_units = cfg.num_pattern_units
        unit_keys = jax.random.split(ks[2], n_units)

        def init_unit(k):
            kk = jax.random.split(k, len(cfg.block_pattern))
            return tuple(block_init(kk[j], kind, cfg)
                         for j, kind in enumerate(cfg.block_pattern))

        params["units"] = jax.vmap(init_unit)(unit_keys) if n_units else ()
        params["rem"] = tuple(
            block_init(ks[3 + j], kind, cfg)
            for j, kind in enumerate(cfg.pattern_remainder))
        return params

    # ---- helpers ---------------------------------------------------------
    def _embed(self, params, tokens):
        x = params["embed"][tokens].astype(self.cfg.dtype)
        return x

    def _logits(self, params, x):
        x = L.apply_norm(params["final_norm"], x, self.cfg)
        head = params["embed"].T if self.cfg.tie_embeddings else params["lm_head"]
        return x @ head.astype(x.dtype)

    # ---- training --------------------------------------------------------
    def forward(self, params: Params, tokens: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """tokens (B,S) -> (logits (B,S,V), moe_aux scalar)."""
        cfg = self.cfg
        b, s = tokens.shape
        x = self._embed(params, tokens)
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

        def unit_body(carry, unit_params):
            x, aux = carry
            x = constrain_acts(x, cfg)
            for j, kind in enumerate(cfg.block_pattern):
                x, a = block_apply(unit_params[j], x, kind, cfg,
                                   positions=positions)
                aux = aux + a
            return (constrain_acts(x, cfg), aux), None

        body = jax.checkpoint(unit_body) if cfg.remat else unit_body
        aux0 = jnp.zeros((), jnp.float32)
        if cfg.num_pattern_units:
            (x, aux), _ = jax.lax.scan(body, (x, aux0), params["units"])
        else:
            aux = aux0
        for j, kind in enumerate(cfg.pattern_remainder):
            x, a = block_apply(params["rem"][j], x, kind, cfg, positions=positions)
            aux = aux + a
        return self._logits(params, x), aux

    def loss(self, params: Params, batch: Dict[str, jax.Array]) -> Tuple[jax.Array, Dict]:
        logits, aux = self.forward(params, batch["tokens"])
        logits = logits.astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        tgt = batch["targets"]
        nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
        mask = batch.get("mask", jnp.ones_like(tgt, jnp.float32))
        ce = (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
        total = ce + aux
        return total, {"ce": ce, "moe_aux": aux}

    # ---- serving ---------------------------------------------------------
    def init_cache(self, batch: int, max_len: int, dtype=None) -> Params:
        cfg = self.cfg
        dtype = dtype or jnp.dtype(cfg.dtype)
        n_units = cfg.num_pattern_units

        def one(kind):
            return block_cache_init(kind, cfg, batch, max_len, dtype)

        units = tuple(
            jax.tree.map(lambda a: jnp.broadcast_to(a, (n_units,) + a.shape), one(kind))
            for kind in cfg.block_pattern) if n_units else ()
        rem = tuple(one(kind) for kind in cfg.pattern_remainder)
        return {"units": units, "rem": rem,
                "pos": jnp.zeros((), jnp.int32)}

    def prefill(self, params: Params, tokens: jax.Array,
                max_len: int) -> Tuple[jax.Array, Params]:
        """Full-sequence forward that fills caches. Returns (last logits, cache)."""
        cfg = self.cfg
        b, s = tokens.shape
        x = self._embed(params, tokens)
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

        def unit_body(x, unit_params):
            x = constrain_acts(x, cfg)
            caches = []
            for j, kind in enumerate(cfg.block_pattern):
                x, c = block_prefill(unit_params[j], x, kind, cfg,
                                     positions=positions, max_len=max_len)
                caches.append(c)
            return constrain_acts(x, cfg), tuple(caches)

        if cfg.num_pattern_units:
            x, unit_caches = jax.lax.scan(unit_body, x, params["units"])
        else:
            unit_caches = ()
        rem_caches = []
        for j, kind in enumerate(cfg.pattern_remainder):
            x, c = block_prefill(params["rem"][j], x, kind, cfg,
                                 positions=positions, max_len=max_len)
            rem_caches.append(c)
        logits = self._logits(params, x[:, -1:, :])
        cache = {"units": unit_caches, "rem": tuple(rem_caches),
                 "pos": jnp.asarray(s, jnp.int32)}
        return logits[:, 0], cache

    def decode_step(self, params: Params, cache: Params,
                    token: jax.Array) -> Tuple[jax.Array, Params]:
        """token (B,) int32 -> (logits (B,V), cache)."""
        cfg = self.cfg
        x = self._embed(params, token[:, None])
        pos = cache["pos"]

        def unit_body(x, scanned):
            unit_params, unit_cache = scanned
            new_caches = []
            for j, kind in enumerate(cfg.block_pattern):
                x, c = block_decode(unit_params[j], x, kind, cfg,
                                    cache=unit_cache[j], pos=pos)
                new_caches.append(c)
            return x, tuple(new_caches)

        if cfg.num_pattern_units:
            x, unit_caches = jax.lax.scan(unit_body, x,
                                          (params["units"], cache["units"]))
        else:
            unit_caches = ()
        rem_caches = []
        for j, kind in enumerate(cfg.pattern_remainder):
            x, c = block_decode(params["rem"][j], x, kind, cfg,
                                cache=cache["rem"][j], pos=pos)
            rem_caches.append(c)
        logits = self._logits(params, x)[:, 0]
        return logits, {"units": unit_caches, "rem": tuple(rem_caches),
                        "pos": pos + 1}
