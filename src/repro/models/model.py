"""Model assembly for all assigned architecture families.

One ``Model`` class covers: dense decoder LMs (stablelm / llama3 / yi /
gemma2 incl. local-global alternation + softcaps), MoE LMs (deepseek-moe,
granite-moe), RWKV-6, Mamba2-hybrid (zamba2), encoder-decoder
(seamless-m4t, frame-embedding stub) and VLM (llava-next, patch-embedding
stub).  Layer stacks run under ``jax.lax.scan`` with stacked parameters so
HLO size and compile time stay flat in depth; bodies are rematerialized in
training.  All entry points are pure functions of (params, batch[, cache]).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig, SHAPES, ShapeSpec
from . import layers as L
from . import moe as MOE
from . import rwkv as RW
from . import ssm as SSM


def _split_tree(key, n):
    return list(jax.random.split(key, n))


def _stack_init(fn, key, n):
    """vmap an init fn over n layer keys -> stacked (n, ...) params."""
    return jax.vmap(fn)(jax.random.split(key, n))


@dataclasses.dataclass
class Model:
    cfg: ArchConfig

    def _ckpt(self, fn):
        if self.cfg.remat_policy == "dots":
            return jax.checkpoint(
                fn,
                policy=jax.checkpoint_policies
                .dots_with_no_batch_dims_saveable)
        return jax.checkpoint(fn)

    # ------------------------------------------------------------------ init
    def init_params(self, key) -> dict:
        cfg = self.cfg
        dt = L.dtype_of(cfg.param_dtype)
        keys = jax.random.split(key, 8)
        p: dict[str, Any] = {
            "embed": L.embed_init(keys[0], cfg.padded_vocab, cfg.d_model,
                                  dt),
            "final_norm": L.norm_init(cfg.d_model, cfg.norm, dt),
        }
        if not cfg.tie_embeddings:
            p["lm_head"] = L.dense_init(keys[1], cfg.d_model,
                                        cfg.padded_vocab, dt)

        fam = cfg.family
        if fam in ("dense", "moe", "vlm"):
            def layer_init(k):
                ks = jax.random.split(k, 4)
                lp = {
                    "ln1": L.norm_init(cfg.d_model, cfg.norm, dt),
                    "attn": L.attention_init(ks[0], cfg.d_model, cfg.n_heads,
                                             cfg.kv_heads, cfg.head_dim, dt),
                    "ln2": L.norm_init(cfg.d_model, cfg.norm, dt),
                }
                if fam == "moe":
                    lp["moe"] = MOE.moe_init(ks[1], cfg, dt)
                else:
                    lp["mlp"] = L.mlp_init(ks[1], cfg.d_model, cfg.d_ff, dt)
                return lp
            p["layers"] = _stack_init(layer_init, keys[2], cfg.layers)
        elif fam == "rwkv":
            def layer_init(k):
                ks = jax.random.split(k, 2)
                return {
                    "ln1": L.norm_init(cfg.d_model, cfg.norm, dt),
                    "time": RW.rwkv_time_init(ks[0], cfg, dt),
                    "ln2": L.norm_init(cfg.d_model, cfg.norm, dt),
                    "chan": RW.rwkv_channel_init(ks[1], cfg, dt),
                }
            p["layers"] = _stack_init(layer_init, keys[2], cfg.layers)
        elif fam in ("ssm", "hybrid"):
            def layer_init(k):
                return {"ln": L.norm_init(cfg.d_model, cfg.norm, dt),
                        "ssm": SSM.ssm_init(k, cfg, dt)}
            p["layers"] = _stack_init(layer_init, keys[2], cfg.layers)
            if fam == "hybrid":
                ks = jax.random.split(keys[3], 2)
                p["shared_block"] = {
                    "ln1": L.norm_init(cfg.d_model, cfg.norm, dt),
                    "attn": L.attention_init(ks[0], cfg.d_model,
                                             cfg.n_heads, cfg.kv_heads,
                                             cfg.head_dim, dt),
                    "ln2": L.norm_init(cfg.d_model, cfg.norm, dt),
                    "mlp": L.mlp_init(ks[1], cfg.d_model, cfg.d_ff, dt),
                }
        elif fam == "encdec":
            def enc_init(k):
                ks = jax.random.split(k, 2)
                return {
                    "ln1": L.norm_init(cfg.d_model, cfg.norm, dt),
                    "attn": L.attention_init(ks[0], cfg.d_model, cfg.n_heads,
                                             cfg.kv_heads, cfg.head_dim, dt),
                    "ln2": L.norm_init(cfg.d_model, cfg.norm, dt),
                    "mlp": L.mlp_init(ks[1], cfg.d_model, cfg.d_ff, dt),
                }

            def dec_init(k):
                ks = jax.random.split(k, 3)
                return {
                    "ln1": L.norm_init(cfg.d_model, cfg.norm, dt),
                    "attn": L.attention_init(ks[0], cfg.d_model, cfg.n_heads,
                                             cfg.kv_heads, cfg.head_dim, dt),
                    "lnx": L.norm_init(cfg.d_model, cfg.norm, dt),
                    "xattn": L.attention_init(ks[1], cfg.d_model,
                                              cfg.n_heads, cfg.kv_heads,
                                              cfg.head_dim, dt),
                    "ln2": L.norm_init(cfg.d_model, cfg.norm, dt),
                    "mlp": L.mlp_init(ks[2], cfg.d_model, cfg.d_ff, dt),
                }
            p["encoder"] = _stack_init(enc_init, keys[2],
                                       cfg.encoder_layers)
            p["layers"] = _stack_init(dec_init, keys[3], cfg.layers)
            p["enc_norm"] = L.norm_init(cfg.d_model, cfg.norm, dt)
        else:
            raise ValueError(f"unknown family {fam}")
        return p

    # ---------------------------------------------------------------- embed
    @jax.named_scope("embed")
    def _embed_in(self, params, tokens, prefix: jnp.ndarray | None):
        cfg = self.cfg
        x = L.embed(params["embed"], tokens)
        if cfg.name.startswith("gemma2"):
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
        if prefix is not None:
            x = jnp.concatenate([prefix.astype(x.dtype), x], axis=1)
        return x.astype(L.dtype_of(cfg.compute_dtype))

    @jax.named_scope("lm_head")
    def _lm_logits(self, params, x):
        cfg = self.cfg
        x = L.apply_norm(params["final_norm"], x, cfg.norm)
        w = (params["embed"]["e"].T if cfg.tie_embeddings
             else params["lm_head"]["w"])
        ldt = L.dtype_of(cfg.logits_dtype)
        if ldt != jnp.float32:
            # §Perf: bf16 lm_head matmul + bf16 logits tensor (f32 accum;
            # the loss upcasts inside log_softmax)
            logits = jnp.dot(x.astype(ldt), w.astype(ldt),
                             preferred_element_type=jnp.float32
                             ).astype(ldt)
        else:
            logits = x.astype(jnp.float32) @ w.astype(jnp.float32)
        if cfg.logit_softcap:
            logits = cfg.logit_softcap * jnp.tanh(logits
                                                  / cfg.logit_softcap)
        if cfg.padded_vocab != cfg.vocab:
            # §Perf vocab padding: mask the pad rows out of the softmax
            pad_mask = jnp.where(jnp.arange(cfg.padded_vocab) < cfg.vocab,
                                 0.0, -1e9)
            logits = logits + pad_mask
        return logits

    # -------------------------------------------------------- layer bodies
    def _attn_block(self, lp, x, positions, *, window_flag=None,
                    cache=None, cache_index=None, layer=None, remat=False):
        cfg = self.cfg

        def body(lp, x, cache):
            h, new_cache = L.attention_apply(
                lp["attn"], L.apply_norm(lp["ln1"], x, cfg.norm),
                n_heads=cfg.n_heads, kv_heads=cfg.kv_heads,
                head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
                q_positions=positions, causal=True,
                window=cfg.window, window_active=window_flag,
                softcap=cfg.attn_softcap,
                cache=cache, cache_index=cache_index, layer=layer)
            x = x + h
            if "moe" in lp:
                h, aux = MOE.moe_apply(lp["moe"], cfg,
                                       L.apply_norm(lp["ln2"], x, cfg.norm))
            else:
                h = L.mlp_apply(lp["mlp"],
                                L.apply_norm(lp["ln2"], x, cfg.norm),
                                use_fused=cfg.fused_mlp)
                aux = jnp.zeros((), jnp.float32)
            return x + h, new_cache, aux
        if remat:
            body = self._ckpt(body)
        return body(lp, x, cache)

    # ----------------------------------------------------------- forward LM
    def _forward_stack(self, params, x, positions, *, caches=None,
                       cache_index=None, remat=False):
        """Scan the layer stack.  caches: pytree stacked on axis 0 or None.
        Returns (x, new_caches, aux_sum)."""
        cfg = self.cfg
        fam = cfg.family
        if cfg.gather_in_compute_dtype:
            # §Perf: cast fp32 masters to compute dtype on their shards so
            # the per-layer FSDP all-gather moves half the bytes
            cdt = L.dtype_of(cfg.compute_dtype)
            params = dict(params)
            params["layers"] = jax.tree.map(
                lambda a: a.astype(cdt)
                if a.dtype == jnp.float32 else a, params["layers"])

        if fam in ("dense", "moe", "vlm"):
            n = cfg.layers
            layer_ids = jnp.arange(n)
            if caches is not None and self.carries_cache(
                    params, {"layers": caches}, x, cache_index):
                # the decode kernel reads a layer of the stacked cache in
                # place: carry the whole stack and write each layer's new
                # rows into it, so no layer is sliced out or stacked back
                def carried_body(carry, inp):
                    x, cache = carry
                    lp, lid = inp
                    wf = (lid % 2 == 0) if cfg.alt_local_global else None
                    y, cache, aux = self._attn_block(
                        lp, x, positions, window_flag=wf, cache=cache,
                        cache_index=cache_index, layer=lid, remat=remat)
                    return (y, cache), aux

                (x, new_caches), auxs = jax.lax.scan(
                    carried_body, (x, caches),
                    (params["layers"], layer_ids))
                return x, new_caches, jnp.sum(auxs)

            def scan_body(carry, inp):
                x = carry
                lp, lid, cache = inp
                wf = None
                if cfg.alt_local_global:
                    wf = (lid % 2 == 0)      # even layers local
                y, new_cache, aux = self._attn_block(
                    lp, x, positions, window_flag=wf, cache=cache,
                    cache_index=cache_index, remat=remat)
                return y, (new_cache, aux)

            x, (new_caches, auxs) = jax.lax.scan(
                scan_body, x, (params["layers"], layer_ids, caches))
            return x, new_caches, jnp.sum(auxs)

        if fam == "rwkv":
            def scan_body(carry, inp):
                x = carry
                lp, cache = inp

                def body(lp, x, cache):
                    state = cache["state"] if cache else None
                    tshift = cache["tshift"] if cache else None
                    cshift = cache["cshift"] if cache else None
                    h, (state, tshift) = RW.rwkv_time_apply(
                        lp["time"], cfg,
                        L.apply_norm(lp["ln1"], x, cfg.norm),
                        state=state, shift=tshift,
                        decode=cache_index is not None)
                    x = x + h
                    h, cshift = RW.rwkv_channel_apply(
                        lp["chan"], cfg,
                        L.apply_norm(lp["ln2"], x, cfg.norm), shift=cshift)
                    x = x + h
                    return x, {"state": state, "tshift": tshift,
                               "cshift": cshift}
                if remat:
                    body = self._ckpt(body)
                x, new_cache = body(lp, x, cache)
                return x, (new_cache, jnp.zeros((), jnp.float32))

            x, (new_caches, auxs) = jax.lax.scan(
                scan_body, x, (params["layers"], caches))
            return x, new_caches, jnp.sum(auxs)

        if fam in ("ssm", "hybrid"):
            period = cfg.attn_every if fam == "hybrid" else cfg.layers
            n_groups = cfg.layers // period
            lp_grouped = jax.tree.map(
                lambda a: a.reshape((n_groups, period) + a.shape[1:]),
                params["layers"])
            shared = params.get("shared_block")

            def scan_group(carry, inp):
                x = carry
                gp, gcache = inp

                def inner(carry2, inp2):
                    x2 = carry2
                    lp, lcache = inp2

                    def body(lp, x2, lcache):
                        state = lcache["state"] if lcache else None
                        cst = lcache["conv"] if lcache else None
                        h, (state, cst) = SSM.ssm_apply(
                            lp["ssm"], cfg,
                            L.apply_norm(lp["ln"], x2, cfg.norm),
                            state=state, conv_state=cst,
                            decode=cache_index is not None)
                        return x2 + h, {"state": state, "conv": cst}
                    if remat:
                        body = self._ckpt(body)
                    x2, new_lcache = body(lp, x2, lcache)
                    return x2, new_lcache

                ssm_caches = gcache["ssm"] if gcache else None
                x, new_ssm = jax.lax.scan(inner, x, (gp, ssm_caches))
                new_gcache = {"ssm": new_ssm}
                if shared is not None:
                    acache = gcache["attn"] if gcache else None
                    x, new_attn, _ = self._attn_block(
                        shared, x, positions, cache=acache,
                        cache_index=cache_index, remat=remat)
                    new_gcache["attn"] = new_attn
                return x, (new_gcache, jnp.zeros((), jnp.float32))

            group_caches = caches
            x, (new_caches, auxs) = jax.lax.scan(
                scan_group, x, (lp_grouped, group_caches))
            return x, new_caches, jnp.sum(auxs)

        raise ValueError(f"_forward_stack does not handle {fam}")

    # ------------------------------------------------------------- encoder
    def _encode(self, params, enc_embeds, remat=False):
        cfg = self.cfg
        pos = jnp.arange(enc_embeds.shape[1])

        def scan_body(x, lp):
            def body(lp, x):
                h, _ = L.attention_apply(
                    lp["attn"], L.apply_norm(lp["ln1"], x, cfg.norm),
                    n_heads=cfg.n_heads, kv_heads=cfg.kv_heads,
                    head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
                    q_positions=pos, causal=False)
                x = x + h
                h = L.mlp_apply(lp["mlp"],
                                L.apply_norm(lp["ln2"], x, cfg.norm))
                return x + h
            if remat:
                body = self._ckpt(body)
            return body(lp, x), None

        x, _ = jax.lax.scan(scan_body, enc_embeds, params["encoder"])
        return L.apply_norm(params["enc_norm"], x, cfg.norm)

    def _decode_stack_encdec(self, params, x, enc_out, positions, *,
                             caches=None, cache_index=None, remat=False):
        cfg = self.cfg

        def scan_body(carry, inp):
            x = carry
            lp, cache = inp

            def body(lp, x, cache):
                self_cache = cache["self"] if cache else None
                h, new_self = L.attention_apply(
                    lp["attn"], L.apply_norm(lp["ln1"], x, cfg.norm),
                    n_heads=cfg.n_heads, kv_heads=cfg.kv_heads,
                    head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
                    q_positions=positions, causal=True,
                    cache=self_cache, cache_index=cache_index)
                x = x + h
                h, _ = L.attention_apply(
                    lp["xattn"], L.apply_norm(lp["lnx"], x, cfg.norm),
                    n_heads=cfg.n_heads, kv_heads=cfg.kv_heads,
                    head_dim=cfg.head_dim, rope_theta=None,
                    q_positions=positions, causal=False, xkv=enc_out)
                x = x + h
                h = L.mlp_apply(lp["mlp"],
                                L.apply_norm(lp["ln2"], x, cfg.norm))
                return x + h, {"self": new_self}
            if remat:
                body = self._ckpt(body)
            x, new_cache = body(lp, x, cache)
            return x, new_cache

        x, new_caches = jax.lax.scan(scan_body, x,
                                     (params["layers"], caches))
        return x, new_caches

    # ------------------------------------------------------------ training
    def loss(self, params, batch, *, remat: bool = True):
        """batch: tokens (B,S) int32, labels (B,S) int32 (-1 = masked),
        plus 'frames'/'patches' (B,F,d) for frontend archs."""
        cfg = self.cfg
        tokens, labels = batch["tokens"], batch["labels"]
        prefix = batch.get("frames") if cfg.family == "encdec" else \
            batch.get("patches")
        if cfg.family == "encdec":
            enc = self._encode(params,
                               batch["frames"].astype(
                                   L.dtype_of(cfg.compute_dtype)),
                               remat=remat)
            x = self._embed_in(params, tokens, None)
            pos = jnp.arange(tokens.shape[1])
            x, _ = self._decode_stack_encdec(params, x, enc, pos,
                                             remat=remat)
        else:
            x = self._embed_in(params, tokens, prefix)
            pos = jnp.arange(x.shape[1])
            x, _, aux = self._forward_stack(params, x, pos, remat=remat)
            if prefix is not None:
                x = x[:, prefix.shape[1]:]
        logits = self._lm_logits(params, x)
        valid = (labels >= 0).astype(jnp.float32)
        labels_safe = jnp.maximum(labels, 0)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, labels_safe[..., None],
                                   axis=-1)[..., 0]
        loss = jnp.sum(nll * valid) / jnp.maximum(jnp.sum(valid), 1.0)
        if cfg.family != "encdec" and cfg.n_experts:
            loss = loss + 0.01 * aux
        return loss

    # ------------------------------------------------------------- serving
    def prefill(self, params, batch, max_len: int):
        """Returns (last-token logits, cache)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        caches = self.init_cache(B, max_len)
        if cfg.family == "encdec":
            enc = self._encode(params, batch["frames"].astype(
                L.dtype_of(cfg.compute_dtype)))
            x = self._embed_in(params, tokens, None)
            pos = jnp.arange(S)
            x, new_caches = self._decode_stack_encdec(
                params, x, enc, pos, caches=caches["layers"],
                cache_index=None)
            new_caches = {"layers": new_caches, "enc_out": enc}
        else:
            prefix = batch.get("patches") if cfg.family == "vlm" else None
            x = self._embed_in(params, tokens, prefix)
            pos = jnp.arange(x.shape[1])
            x, lcaches, _ = self._forward_stack(
                params, x, pos, caches=caches["layers"], cache_index=None)
            new_caches = {"layers": lcaches}
        logits = self._lm_logits(params, x[:, -1:])
        return logits, new_caches

    def decode_step(self, params, cache, tokens, index):
        """One cache-resident step: single tokens, chunks, or slots.

        tokens: (B, S) — S == 1 is the classic decode step; S > 1 is a
        chunked-prefill continuation (the chunk is written to the cache
        at [index, index+S) with causal self-attention over cache+chunk).
        index: scalar int32 write position shared by all rows, or an
        int32 (B,) vector of per-row positions (slot-indexed decode for
        the continuous-batching scheduler; attention masks each row at
        its own valid length).
        """
        cfg = self.cfg
        x = self._embed_in(params, tokens, None)
        B, S = tokens.shape
        index = jnp.asarray(index, jnp.int32)
        offs = jnp.arange(S, dtype=jnp.int32)
        if index.ndim == 1:
            pos = index[:, None] + offs[None, :]         # (B, S)
        else:
            pos = jnp.broadcast_to(index + offs, (B, S))
        if cfg.family == "encdec":
            x, new_l = self._decode_stack_encdec(
                params, x, cache["enc_out"], pos,
                caches=cache["layers"], cache_index=index)
            new_cache = {"layers": new_l, "enc_out": cache["enc_out"]}
        else:
            x, new_l, _ = self._forward_stack(
                params, x, pos, caches=cache["layers"], cache_index=index)
            new_cache = {"layers": new_l}
        return self._lm_logits(params, x), new_cache

    def carries_cache(self, params, cache, x, index) -> bool:
        """Whether a ``decode_step`` of ``x`` (tokens (B, S), or their
        activations) against ``cache`` at write position(s) ``index``
        carries the stacked KV cache through the layer scan and updates
        it in place: where the decode kernel reads it
        (``layers.decode_kernel_applies``), with the weights on the same
        one device.  Every other step slices each layer out and stacks it
        back.  Traced or concrete arguments: the engine donates the cache
        exactly where this holds."""
        return (self.cfg.family in ("dense", "moe", "vlm")
                and L.decode_kernel_applies(x, cache["layers"]["k"], index,
                                            params["embed"]["e"]))

    # -------------------------------------------------------------- caches
    def init_cache(self, batch: int, max_len: int) -> dict:
        cfg = self.cfg
        dt = L.dtype_of(cfg.compute_dtype)
        n = cfg.layers

        def kv(n_layers, length):
            return {"k": jnp.zeros((n_layers, batch, length,
                                    cfg.kv_heads, cfg.head_dim), dt),
                    "v": jnp.zeros((n_layers, batch, length,
                                    cfg.kv_heads, cfg.head_dim), dt)}
        fam = cfg.family
        if fam in ("dense", "moe", "vlm"):
            return {"layers": kv(n, max_len)}
        if fam == "rwkv":
            nh, hd = RW.rwkv_dims(cfg)
            return {"layers": {
                "state": jnp.zeros((n, batch, nh, hd, hd), jnp.float32),
                "tshift": jnp.zeros((n, batch, 1, cfg.d_model), dt),
                "cshift": jnp.zeros((n, batch, 1, cfg.d_model), dt),
            }}
        if fam in ("ssm", "hybrid"):
            period = cfg.attn_every if fam == "hybrid" else cfg.layers
            n_groups = n // period
            d_inner, nh, hd, ns = SSM.ssm_dims(cfg)
            conv_dim = d_inner + 2 * ns
            out = {"ssm": {
                "state": jnp.zeros((n_groups, period, batch, nh, hd, ns),
                                   jnp.float32),
                "conv": jnp.zeros((n_groups, period, batch,
                                   cfg.conv_kernel - 1, conv_dim), dt),
            }}
            if fam == "hybrid":
                out["attn"] = kv(n_groups, max_len)
            return {"layers": out}
        if fam == "encdec":
            return {"layers": {"self": kv(n, max_len)}}
        raise ValueError(fam)

    # --------------------------------------------------------- input specs
    def input_specs(self, shape: ShapeSpec | str) -> dict:
        """ShapeDtypeStruct stand-ins for every model input (dry-run)."""
        cfg = self.cfg
        if isinstance(shape, str):
            shape = SHAPES[shape]
        B, S = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        cdt = L.dtype_of(cfg.compute_dtype)
        sds = jax.ShapeDtypeStruct
        if shape.kind == "train":
            out = {"tokens": sds((B, S), i32), "labels": sds((B, S), i32)}
            if cfg.family == "encdec":
                out["frames"] = sds((B, cfg.frontend_len or S, cfg.d_model),
                                    cdt)
            if cfg.family == "vlm":
                out["patches"] = sds((B, cfg.frontend_len, cfg.d_model), cdt)
            return out
        if shape.kind == "prefill":
            out = {"tokens": sds((B, S), i32)}
            if cfg.family == "encdec":
                out["frames"] = sds((B, cfg.frontend_len or S, cfg.d_model),
                                    cdt)
            if cfg.family == "vlm":
                out["patches"] = sds((B, cfg.frontend_len, cfg.d_model), cdt)
            return out
        # decode: one new token against a cache of size S
        out = {"tokens": sds((B, 1), i32),
               "index": sds((), i32),
               "cache": jax.eval_shape(
                   lambda: self.init_cache(B, S))}
        if cfg.family == "encdec":
            enc_len = cfg.frontend_len or S
            out["cache"] = jax.eval_shape(
                lambda: {**self.init_cache(B, S),
                         "enc_out": jnp.zeros((B, enc_len, cfg.d_model),
                                              cdt)})
        return out


def build_model(cfg: ArchConfig) -> Model:
    return Model(cfg)
