"""Unified model API over all assigned architecture families.

    lm = build_model(cfg)                       # cfg: ModelConfig
    params = lm.init(key, dtype)
    loss, metrics = lm.loss(params, batch)      # train / prefill
    cache = lm.init_cache(batch_size, max_seq, window=...)
    logits, cache = lm.decode_step(params, cache, tokens, pos, window=...)

Batch dict:  tokens (B,S) int32, labels (B,S) int32,
             + patches (B, n_patches, d) for VLM,
             + frames (B, enc_seq, d) for audio (stub frontends).

Train/prefill paths scan over stacked layer params (compile-time O(1) in
depth) with optional remat; decode scans where caches are homogeneous and
unrolls otherwise (xLSTM, zamba2).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import mlp as mlp_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.layers import embed_init, rms_norm, split_keys

Params = Dict[str, Any]
Batch = Dict[str, jax.Array]


def _stack_init(init_one, keys):
    return jax.vmap(init_one)(jnp.stack(keys))


def chunked_lm_loss(x: jax.Array, unembed: jax.Array, labels: jax.Array,
                    chunk: int = 512) -> jax.Array:
    """Cross-entropy without materializing (B,S,V) fp32 logits.

    x: (B,S,d) final hiddens; unembed: (d,V); labels: (B,S) int32.
    Positions with label < 0 are masked out.
    """
    B, S, d = x.shape
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    Sp = S + pad
    nc = Sp // chunk
    xc = x.reshape(B, nc, chunk, d).swapaxes(0, 1)
    lc = labels.reshape(B, nc, chunk).swapaxes(0, 1)

    def step(carry, c):
        tot, cnt = carry
        xi, li = c
        logits = jnp.einsum("bsd,dv->bsv", xi, unembed).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, jnp.maximum(li, 0)[..., None],
                                   axis=-1)[..., 0]
        mask = (li >= 0).astype(jnp.float32)
        tot = tot + jnp.sum((logz - gold) * mask)
        cnt = cnt + jnp.sum(mask)
        return (tot, cnt), None

    (tot, cnt), _ = jax.lax.scan(step, (jnp.zeros((), jnp.float32),
                                        jnp.zeros((), jnp.float32)), (xc, lc))
    return tot / jnp.maximum(cnt, 1.0)


# ===========================================================================
class LM:
    def __init__(self, cfg: ModelConfig, *, remat: bool = True,
                 moe_mode: str = "onehot", moe_group_tokens: int = 512,
                 kv_chunk: int = 1024, remat_groups: int = 0,
                 attn_backend: str = "jnp"):
        self.cfg = cfg
        self.remat = remat
        self.moe_mode = moe_mode
        self.moe_group_tokens = moe_group_tokens
        self.kv_chunk = kv_chunk
        self.attn_backend = attn_backend
        # remat_groups > 0: nested-remat — scan over `remat_groups` groups of
        # layers, checkpointing only each GROUP's input instead of every
        # layer's (residual stack shrinks L/remat_groups-fold; backward
        # recomputes one group at a time). §Perf knob.
        self.remat_groups = remat_groups

    # ---------------- init -------------------------------------------
    def init(self, key, dtype=jnp.float32) -> Params:
        cfg = self.cfg
        ks = split_keys(key, 8)
        p: Params = {
            "embed": embed_init(ks[0], (cfg.vocab, cfg.d_model), dtype),
            "ln_f": jnp.ones((cfg.d_model,), dtype),
        }
        if not cfg.tie_embeddings:
            p["unembed"] = embed_init(ks[1], (cfg.d_model, cfg.vocab), dtype)

        def init_dense_block(k):
            k1, k2 = jax.random.split(k)
            blk = {"ln1": jnp.ones((cfg.d_model,), dtype),
                   "ln2": jnp.ones((cfg.d_model,), dtype),
                   "attn": attn.init_attention(k1, cfg.d_model, cfg.n_heads,
                                               cfg.n_kv_heads, cfg.head_dim,
                                               cfg.qkv_bias, dtype)}
            if cfg.moe is not None:
                blk["ffn"] = moe_mod.init_moe(k2, cfg.d_model, cfg.moe, dtype)
            else:
                blk["ffn"] = mlp_mod.init_swiglu(k2, cfg.d_model, cfg.d_ff, dtype)
            return blk

        fam = cfg.family
        if fam in ("dense", "moe", "vlm", "audio"):
            p["blocks"] = _stack_init(init_dense_block,
                                      split_keys(ks[2], cfg.n_layers))
        if fam == "vlm":
            p["patch_proj"] = embed_init(ks[3], (cfg.d_model, cfg.d_model), dtype)
        if fam == "audio":
            def init_enc_block(k):
                k1, k2 = jax.random.split(k)
                return {"ln1": jnp.ones((cfg.d_model,), dtype),
                        "ln2": jnp.ones((cfg.d_model,), dtype),
                        "attn": attn.init_attention(k1, cfg.d_model, cfg.n_heads,
                                                    cfg.n_kv_heads, cfg.head_dim,
                                                    False, dtype),
                        "mlp": mlp_mod.init_gelu(k2, cfg.d_model, cfg.d_ff, dtype)}

            def init_dec_block(k):
                k1, k2, k3 = jax.random.split(k, 3)
                return {"ln1": jnp.ones((cfg.d_model,), dtype),
                        "ln2": jnp.ones((cfg.d_model,), dtype),
                        "ln3": jnp.ones((cfg.d_model,), dtype),
                        "self": attn.init_attention(k1, cfg.d_model, cfg.n_heads,
                                                    cfg.n_kv_heads, cfg.head_dim,
                                                    False, dtype),
                        "cross": attn.init_attention(k2, cfg.d_model, cfg.n_heads,
                                                     cfg.n_kv_heads, cfg.head_dim,
                                                     False, dtype),
                        "mlp": mlp_mod.init_gelu(k3, cfg.d_model, cfg.d_ff, dtype)}

            p["enc_blocks"] = _stack_init(init_enc_block,
                                          split_keys(ks[3], cfg.enc_layers))
            p["blocks"] = _stack_init(init_dec_block,
                                      split_keys(ks[2], cfg.n_layers))
            p["enc_pos"] = embed_init(ks[4], (cfg.enc_seq, cfg.d_model), dtype)
            p["enc_ln_f"] = jnp.ones((cfg.d_model,), dtype)
        if fam == "hybrid":
            def init_mamba_block(k):
                return {"ln": jnp.ones((cfg.d_model,), dtype),
                        "mamba": ssm_mod.init_mamba2(k, cfg.d_model, cfg.ssm, dtype)}
            p["blocks"] = _stack_init(init_mamba_block,
                                      split_keys(ks[2], cfg.n_layers))
            p["shared_ln"] = jnp.ones((cfg.d_model,), dtype)
            p["shared_attn"] = attn.init_attention(
                ks[3], cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
                cfg.qkv_bias, dtype)
        if fam == "ssm":  # xlstm
            xl = cfg.xlstm
            blocks = []
            for i, k in enumerate(split_keys(ks[2], cfg.n_layers)):
                if i in xl.slstm_indices:
                    blocks.append({"slstm": xlstm_mod.init_slstm(k, cfg, dtype)})
                else:
                    blocks.append({"mlstm": xlstm_mod.init_mlstm(k, cfg, dtype)})
            p["blocks"] = blocks
        return p

    def _unembed(self, params: Params) -> jax.Array:
        if self.cfg.tie_embeddings:
            return params["embed"].T
        return params["unembed"]

    # ---------------- forward (train / prefill) ----------------------
    def forward(self, params: Params, batch: Batch, *,
                window: Optional[int] = None) -> Tuple[jax.Array, jax.Array]:
        """Returns (final hiddens (B,S,d), moe aux loss scalar)."""
        cfg = self.cfg
        window = window if window is not None else cfg.sliding_window
        tokens = batch["tokens"]
        x = jnp.take(params["embed"], tokens, axis=0)
        aux = jnp.zeros((), jnp.float32)

        if cfg.family == "vlm":
            patches = jnp.einsum("bpd,de->bpe",
                                 batch["patches"].astype(x.dtype),
                                 params["patch_proj"])
            x = jnp.concatenate([patches, x], axis=1)

        B, S, _ = x.shape
        positions = jnp.arange(S)

        if cfg.family in ("dense", "moe", "vlm"):
            x, aux = self._dense_stack(params["blocks"], x, positions, window)
        elif cfg.family == "audio":
            enc = self._encode(params, batch["frames"])
            x, aux = self._audio_dec_stack(params["blocks"], x, enc,
                                           positions, window)
        elif cfg.family == "hybrid":
            x = self._hybrid_stack(params, x, positions, window)
        elif cfg.family == "ssm":
            x = self._xlstm_stack(params["blocks"], x)
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        if cfg.family == "vlm":  # strip patch positions for the LM head
            x = x[:, batch["patches"].shape[1]:]
        return x, aux

    def _maybe_remat(self, f):
        return jax.checkpoint(f) if self.remat else f

    def _dense_stack(self, blocks, x, positions, window):
        cfg = self.cfg

        def body(carry, blk):
            h, aux = carry
            a = attn.attention_forward(blk["attn"],
                                       rms_norm(h, blk["ln1"], cfg.norm_eps),
                                       positions=positions,
                                       rope_theta=cfg.rope_theta,
                                       window=window, kv_chunk=self.kv_chunk,
                                       backend=self.attn_backend)
            h = h + a
            hin = rms_norm(h, blk["ln2"], cfg.norm_eps)
            if cfg.moe is not None:
                f, a_moe = moe_mod.moe_forward(
                    blk["ffn"], hin, cfg.moe, mode=self.moe_mode,
                    group_tokens=self.moe_group_tokens)
                aux = aux + a_moe
            else:
                f = mlp_mod.mlp_forward(blk["ffn"], hin)
            return (h + f, aux), None

        G = self.remat_groups
        if G and cfg.n_layers % G == 0 and G < cfg.n_layers:
            # nested remat: checkpoint BOTH levels. Forward saves only the G
            # group inputs; backward recomputes one group at a time, itself
            # under per-layer remat (transient residuals = L/G hiddens).
            # §Perf lesson: remat-outer with a plain inner scan is a trap —
            # the inner scan then saves every layer's full internals during
            # the recompute (measured 6x temp blow-up before this fix).
            grouped = jax.tree_util.tree_map(
                lambda leaf: leaf.reshape((G, cfg.n_layers // G) + leaf.shape[1:]),
                blocks)
            inner_body = self._maybe_remat(body)

            def group_body(carry, gblk):
                return jax.lax.scan(inner_body, carry, gblk)

            (x, aux), _ = jax.lax.scan(self._maybe_remat(group_body),
                                       (x, jnp.zeros((), jnp.float32)),
                                       grouped)
        else:
            (x, aux), _ = jax.lax.scan(self._maybe_remat(body),
                                       (x, jnp.zeros((), jnp.float32)),
                                       blocks)
        return x, aux / self.cfg.n_layers

    def _encode(self, params, frames):
        cfg = self.cfg
        x = frames.astype(params["enc_pos"].dtype) + params["enc_pos"][None]

        def body(h, blk):
            h = h + attn.encoder_attention(blk["attn"],
                                           rms_norm(h, blk["ln1"], cfg.norm_eps))
            h = h + mlp_mod.mlp_forward(blk["mlp"],
                                        rms_norm(h, blk["ln2"], cfg.norm_eps))
            return h, None

        x, _ = jax.lax.scan(self._maybe_remat(body), x, params["enc_blocks"])
        return rms_norm(x, params["enc_ln_f"], cfg.norm_eps)

    def _audio_dec_stack(self, blocks, x, enc, positions, window):
        cfg = self.cfg

        def body(carry, blk):
            h, _ = carry
            h = h + attn.attention_forward(
                blk["self"], rms_norm(h, blk["ln1"], cfg.norm_eps),
                positions=positions, rope_theta=cfg.rope_theta,
                window=window, kv_chunk=self.kv_chunk,
                backend=self.attn_backend)
            h = h + attn.cross_attention(
                blk["cross"], rms_norm(h, blk["ln2"], cfg.norm_eps),
                *attn.cross_kv(blk["cross"], enc))
            h = h + mlp_mod.mlp_forward(blk["mlp"],
                                        rms_norm(h, blk["ln3"], cfg.norm_eps))
            return (h, jnp.zeros((), jnp.float32)), None

        (x, _), _ = jax.lax.scan(self._maybe_remat(body),
                                 (x, jnp.zeros((), jnp.float32)), blocks)
        return x, jnp.zeros((), jnp.float32)

    def _hybrid_stack(self, params, x, positions, window):
        """Zamba2: scan over groups of `attn_every` Mamba2 layers; the
        SHARED attention block (reused weights) closes each group. Group
        scan keeps HLO trip counts static (no lax.cond)."""
        cfg = self.cfg
        shared = params["shared_attn"]
        shared_ln = params["shared_ln"]
        ae = cfg.attn_every
        n_groups = cfg.n_layers // ae
        grouped = jax.tree_util.tree_map(
            lambda leaf: leaf.reshape((n_groups, ae) + leaf.shape[1:]),
            params["blocks"])

        def inner(h, blk):
            h = h + ssm_mod.mamba2_forward(
                blk["mamba"], rms_norm(h, blk["ln"], cfg.norm_eps), cfg.ssm)
            return h, None

        def group(h, gblk):
            h, _ = jax.lax.scan(inner, h, gblk)
            h = h + attn.attention_forward(
                shared, rms_norm(h, shared_ln, cfg.norm_eps),
                positions=positions, rope_theta=cfg.rope_theta,
                window=window, kv_chunk=self.kv_chunk,
                backend=self.attn_backend)
            return h, None

        x, _ = jax.lax.scan(self._maybe_remat(group), x, grouped)
        return x

    def _xlstm_stack(self, blocks, x):
        cfg = self.cfg
        for i, blk in enumerate(blocks):
            # the scopes name each block kind's operations (forward and,
            # under jax.grad, transpose(jvp(...))) in the compiled HLO
            if "slstm" in blk:
                with jax.named_scope("model.slstm"):
                    x = x + xlstm_mod.slstm_forward(blk["slstm"], x, cfg)
            else:
                with jax.named_scope("model.mlstm"):
                    x = x + xlstm_mod.mlstm_forward(blk["mlstm"], x, cfg)
        return x

    # ---------------- loss -------------------------------------------
    def loss(self, params: Params, batch: Batch, *,
             window: Optional[int] = None) -> Tuple[jax.Array, Dict]:
        x, aux = self.forward(params, batch, window=window)
        ce = chunked_lm_loss(x, self._unembed(params), batch["labels"])
        lb = (self.cfg.moe.load_balance_coef if self.cfg.moe else 0.0)
        total = ce + lb * aux
        return total, {"ce": ce, "moe_aux": aux}

    # ---------------- decode -----------------------------------------
    def init_cache(self, batch: int, max_seq: int, *,
                   window: Optional[int] = None, dtype=jnp.bfloat16) -> Any:
        cfg = self.cfg
        window = window if window is not None else cfg.sliding_window
        cap = min(max_seq, window) if window else max_seq
        L = cfg.n_layers
        if cfg.family in ("dense", "moe", "vlm"):
            shape = (L, batch, cap, cfg.n_kv_heads, cfg.head_dim)
            return {"kv": attn.KVCache(jnp.zeros(shape, dtype),
                                       jnp.zeros(shape, dtype))}
        if cfg.family == "audio":
            shape = (L, batch, cap, cfg.n_kv_heads, cfg.head_dim)
            xshape = (L, batch, cfg.enc_seq, cfg.n_kv_heads, cfg.head_dim)
            return {"kv": attn.KVCache(jnp.zeros(shape, dtype),
                                       jnp.zeros(shape, dtype)),
                    "cross": attn.KVCache(jnp.zeros(xshape, dtype),
                                          jnp.zeros(xshape, dtype))}
        if cfg.family == "hybrid":
            n_apps = cfg.n_layers // cfg.attn_every
            shape = (batch, cap, cfg.n_kv_heads, cfg.head_dim)
            return {
                "mamba": [ssm_mod.init_mamba2_state(batch, cfg.d_model,
                                                    cfg.ssm, dtype=dtype)
                          for _ in range(L)],
                "shared": [attn.KVCache(jnp.zeros(shape, dtype),
                                        jnp.zeros(shape, dtype))
                           for _ in range(n_apps)],
            }
        if cfg.family == "ssm":
            states = []
            for i in range(L):
                if i in cfg.xlstm.slstm_indices:
                    states.append(xlstm_mod.init_slstm_state(batch, cfg))
                else:
                    states.append(xlstm_mod.init_mlstm_state(batch, cfg,
                                                             dtype=dtype))
            return {"states": states}
        raise ValueError(cfg.family)

    def prime_cross_cache(self, params: Params, cache, frames):
        """Whisper: run the encoder once, fill per-layer cross K/V."""
        enc = self._encode(params, frames)

        def fill(blk):
            k, v = attn.cross_kv(blk["cross"], enc)
            return k, v

        ks, vs = jax.vmap(fill)(params["blocks"])  # vmap over layer axis
        dt = cache["cross"].k.dtype
        return dict(cache, cross=attn.KVCache(ks.astype(dt), vs.astype(dt)))

    def decode_step(self, params: Params, cache, tokens: jax.Array,
                    pos: jax.Array, *, window: Optional[int] = None
                    ) -> Tuple[jax.Array, Any]:
        """tokens: (B,1) int32; pos: () int32. Returns (logits (B,1,V), cache)."""
        cfg = self.cfg
        window = window if window is not None else cfg.sliding_window
        x = jnp.take(params["embed"], tokens, axis=0)
        ring = window is not None

        if cfg.family in ("dense", "moe", "vlm"):
            def body(h, xs):
                blk, kc, vc = xs
                hin = rms_norm(h, blk["ln1"], cfg.norm_eps)
                a, new_kv = attn.attention_decode(
                    blk["attn"], hin, attn.KVCache(kc, vc), pos,
                    rope_theta=cfg.rope_theta, ring=ring, window=window)
                h = h + a
                hin = rms_norm(h, blk["ln2"], cfg.norm_eps)
                if cfg.moe is not None:
                    f, _ = moe_mod.moe_forward(blk["ffn"], hin, cfg.moe,
                                               mode=self.moe_mode,
                                               group_tokens=tokens.shape[0])
                else:
                    f = mlp_mod.mlp_forward(blk["ffn"], hin)
                return h + f, new_kv

            x, new_kv = jax.lax.scan(body, x, (params["blocks"],
                                               cache["kv"].k, cache["kv"].v))
            new_cache = {"kv": attn.KVCache(new_kv.k, new_kv.v)}
        elif cfg.family == "audio":
            def body(h, xs):
                blk, kc, vc, xk, xv = xs
                a, new_kv = attn.attention_decode(
                    blk["self"], rms_norm(h, blk["ln1"], cfg.norm_eps),
                    attn.KVCache(kc, vc), pos, rope_theta=cfg.rope_theta,
                    ring=ring, window=window)
                h = h + a
                q = rms_norm(h, blk["ln2"], cfg.norm_eps)
                c = attn.cross_attention(blk["cross"], q, xk, xv)
                h = h + c
                h = h + mlp_mod.mlp_forward(blk["mlp"],
                                            rms_norm(h, blk["ln3"], cfg.norm_eps))
                return h, new_kv

            x, new_kv = jax.lax.scan(body, x, (params["blocks"],
                                               cache["kv"].k, cache["kv"].v,
                                               cache["cross"].k, cache["cross"].v))
            new_cache = dict(cache, kv=attn.KVCache(new_kv.k, new_kv.v))
        elif cfg.family == "hybrid":
            new_m, new_s = [], list(cache["shared"])
            blocks = params["blocks"]
            for i in range(cfg.n_layers):
                blk = jax.tree_util.tree_map(lambda a: a[i], blocks)
                o, st = ssm_mod.mamba2_decode(
                    blk["mamba"], rms_norm(x, blk["ln"], cfg.norm_eps),
                    cache["mamba"][i], cfg.ssm)
                x = x + o
                new_m.append(st)
                if (i + 1) % cfg.attn_every == 0:
                    j = (i + 1) // cfg.attn_every - 1
                    a, kvn = attn.attention_decode(
                        params["shared_attn"],
                        rms_norm(x, params["shared_ln"], cfg.norm_eps),
                        new_s[j], pos, rope_theta=cfg.rope_theta,
                        ring=ring, window=window)
                    x = x + a
                    new_s[j] = kvn
            new_cache = {"mamba": new_m, "shared": new_s}
        elif cfg.family == "ssm":
            new_states = []
            for i, blk in enumerate(params["blocks"]):
                if "slstm" in blk:
                    o, st = xlstm_mod.slstm_decode(blk["slstm"], x,
                                                   cache["states"][i], cfg)
                else:
                    o, st = xlstm_mod.mlstm_decode(blk["mlstm"], x,
                                                   cache["states"][i], cfg)
                x = x + o
                new_states.append(st)
            new_cache = {"states": new_states}
        else:
            raise ValueError(cfg.family)

        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        logits = jnp.einsum("bsd,dv->bsv", x, self._unembed(params))
        return logits, new_cache


def build_model(cfg: ModelConfig, **kw) -> LM:
    return LM(cfg, **kw)
