"""Architecture assembly: decoder LMs (dense/moe/xlstm/zamba) and enc-dec.

Full-size configs scan over stacked per-layer parameters (small HLO, fast
SPMD compiles) with per-block rematerialization; tiny configs run
the same code paths on CPU for smoke tests.

Contract (used by core.steps, frontend, examples):
  m = build_model(cfg)
  m.specs()                                  ParamSpec tree
  m.apply(params, batch)                  -> (logits, aux)     train fwd
  m.loss(params, batch)                   -> scalar
  with m.recording() as stats: m.loss(...)   the forward's layer stats
  m.cache_specs(batch, cache_len)            ParamSpec tree (zeros init)
  m.prefill(params, batch, cache_len)     -> (last logits, cache)
  m.decode_step(params, cache, batch, pos)-> (logits, cache)
"""
from __future__ import annotations

import contextlib
import dataclasses

import jax
import jax.numpy as jnp

from ..core.sharding import ParamSpec, act_constrain
from . import attention, blocks, layers


def stack_specs(tree, n: int):
    """Prepend a scanned 'layers' dim to every ParamSpec in a tree."""
    return jax.tree.map(
        lambda s: ParamSpec((n,) + s.shape, ("layers",) + s.dims, s.dtype,
                            s.init, s.scale),
        tree, is_leaf=lambda x: isinstance(x, ParamSpec))


def _maybe_remat(fn, enable: bool):
    return jax.checkpoint(fn, prevent_cse=False) if enable else fn


def reduce_stats(stacked: dict) -> dict:
    """Counters stacked along a leading dim (by a layer scan, or of
    several forwards) -> one: sums, and the largest ``moe_max_load``."""
    return {k: v.max(0) if k == "moe_max_load" else v.sum(0)
            for k, v in stacked.items()}


class _Recorder:
    """``with model.recording() as stats: model.loss(...)``: the step that
    traces the loss gets the forward's layer statistics (``aux``, and the
    MoE counters ``moe_assigned``/``moe_max_load``) as values of its own
    trace, while ``loss`` itself stays a scalar function."""

    _sink = None

    @contextlib.contextmanager
    def recording(self):
        prev, self._sink = self._sink, {}
        try:
            yield self._sink
        finally:
            self._sink = prev

    def _record(self, stats: dict):
        if self._sink is not None:
            self._sink.clear()
            self._sink.update(stats)


# ===========================================================================
# Decoder-only LM (dense / moe / xlstm / zamba)
# ===========================================================================
class LM(_Recorder):
    def __init__(self, cfg):
        self.cfg = cfg

    # -- specs ----------------------------------------------------------------
    def specs(self) -> dict:
        cfg = self.cfg
        sp = {
            "embed": layers.embed_specs(cfg.vocab, cfg.d_model),
            "ln_f": layers.norm_specs(cfg.d_model, cfg.norm),
            "unembed": layers.unembed_specs(cfg.d_model, cfg.vocab),
        }
        fam = cfg.family
        if fam in ("dense", "moe"):
            n_dense = cfg.first_dense if fam == "moe" else 0
            if n_dense:
                sp["dense"] = stack_specs(blocks.tblock_specs(cfg), n_dense)
            sp["stack"] = stack_specs(
                blocks.tblock_specs(cfg, use_moe=(fam == "moe")),
                cfg.n_layers - n_dense)
        elif fam == "xlstm":
            groups = cfg.n_layers // cfg.slstm_every
            per = cfg.slstm_every - 1
            sp["stack"] = {
                "m": stack_specs(stack_specs(blocks.mlstm_block_specs(cfg), per),
                                 groups),
                "s": stack_specs(blocks.slstm_block_specs(cfg), groups),
            }
        elif fam == "zamba":
            groups = cfg.n_layers // cfg.shared_every
            sp["stack"] = {
                "mamba": stack_specs(
                    stack_specs(blocks.mamba_block_specs(cfg),
                                cfg.shared_every), groups),
                "shared": blocks.tblock_specs(cfg),
            }
        else:
            raise ValueError(fam)
        # dtype override for parameters
        sp = jax.tree.map(
            lambda s: dataclasses.replace(s, dtype=cfg.p_dtype)
            if jnp.issubdtype(s.dtype, jnp.floating) else s,
            sp, is_leaf=lambda x: isinstance(x, ParamSpec))
        return sp

    # -- forward ---------------------------------------------------------------
    def _backbone(self, params, x):
        """x: [B, S, D] -> (x, stats): ``aux`` and, for MoE layers that
        count them, ``moe_assigned`` (summed) and ``moe_max_load``
        (largest)."""
        cfg = self.cfg
        fam = cfg.family

        if fam in ("dense", "moe"):
            def body(carry, p):
                h, aux = carry
                h = act_constrain(h, ("batch", "seq", "embed"))
                h, st = blocks.tblock_apply(h, p, cfg)
                # constrain the OUTPUT too: it is what scan saves for the
                # backward pass (the activation-checkpoint stack)
                h = act_constrain(h, ("batch", "seq", "embed"))
                return (h, aux + st.pop("aux")), st
            body = _maybe_remat(body, cfg.remat)
            aux, counts = jnp.zeros((), jnp.float32), {}
            # the leading dense layers, then the uniform stack; only MoE
            # layers give counters
            for name in ("dense", "stack"):
                if name in params:
                    (x, aux), ys = jax.lax.scan(body, (x, aux), params[name])
                    counts.update(reduce_stats(ys))
            return x, {"aux": aux, **counts}

        if fam == "xlstm":
            def m_body(h, p):
                return blocks.mlstm_block_apply(h, p, cfg,
                                                chunk=cfg.ssm_chunk), None

            def g_body(h, gp):
                h, _ = jax.lax.scan(_maybe_remat(m_body, cfg.remat),
                                         h, gp["m"])
                h = blocks.slstm_block_apply(h, gp["s"], cfg)
                return h, None
            x, _ = jax.lax.scan(g_body, x, params["stack"])
            return x, {"aux": jnp.zeros((), jnp.float32)}

        if fam == "zamba":
            shared = params["stack"]["shared"]

            def m_body(h, p):
                return blocks.mamba_block_apply(h, p, cfg,
                                                chunk=cfg.ssm_chunk), None

            def g_body(h, gp):
                h = act_constrain(h, ("batch", "seq", "embed"))
                h, _ = jax.lax.scan(_maybe_remat(m_body, cfg.remat),
                                         h, gp)
                h, _ = blocks.tblock_apply(h, shared, cfg)
                h = act_constrain(h, ("batch", "seq", "embed"))
                return h, None
            g_fn = _maybe_remat(g_body, cfg.remat)
            x, _ = jax.lax.scan(g_fn, x, params["stack"]["mamba"])
            return x, {"aux": jnp.zeros((), jnp.float32)}

        raise ValueError(fam)

    def apply(self, params, batch):
        cfg = self.cfg
        x = layers.embed(batch["tokens"], params["embed"]).astype(cfg.c_dtype)
        x, stats = self._backbone(params, x)
        self._record(stats)
        x = layers.apply_norm(x, params["ln_f"], cfg.norm, cfg.norm_eps)
        return layers.logits(x, params["unembed"]), stats["aux"]

    def loss(self, params, batch):
        lg, aux = self.apply(params, batch)
        mask = batch.get("mask")
        return layers.softmax_xent(lg, batch["labels"], mask) \
            + self.cfg.aux_weight * aux

    # -- decode cache -----------------------------------------------------------
    def _check_servable(self):
        cfg = self.cfg
        if cfg.mla or cfg.first_dense:
            raise ValueError(
                f"{cfg.name}: serving latent attention (MLA) or leading "
                f"dense layers is not supported: there is no latent decode "
                f"cache; such models train and run full forwards only")

    def cache_specs(self, batch: int, cache_len: int) -> dict:
        cfg = self.cfg
        fam = cfg.family
        self._check_servable()
        if fam in ("dense", "moe"):
            return blocks.kv_cache_specs(cfg, batch, cache_len,
                                         prefix=(cfg.n_layers,))
        if fam == "xlstm":
            groups = cfg.n_layers // cfg.slstm_every
            per = cfg.slstm_every - 1
            return {
                "m": blocks.mlstm_state_specs(cfg, batch, prefix=(groups, per)),
                "s": blocks.slstm_state_specs(cfg, batch, prefix=(groups,)),
            }
        if fam == "zamba":
            groups = cfg.n_layers // cfg.shared_every
            return {
                "mamba": blocks.mamba_state_specs(
                    cfg, batch, prefix=(groups, cfg.shared_every)),
                "shared": blocks.kv_cache_specs(cfg, batch, cache_len,
                                                prefix=(groups,)),
            }
        raise ValueError(fam)

    # -- prefill -----------------------------------------------------------------
    def prefill(self, params, batch, cache_len: int):
        cfg = self.cfg
        fam = cfg.family
        self._check_servable()
        x = layers.embed(batch["tokens"], params["embed"]).astype(cfg.c_dtype)

        if fam in ("dense", "moe"):
            def body(h, p):
                h, c = blocks.tblock_prefill(h, p, cfg, cache_len)
                return h, c
            x, cache = jax.lax.scan(_maybe_remat(body, False), x,
                                    params["stack"])
        elif fam == "xlstm":
            def m_body(h, p):
                return blocks.mlstm_block_prefill(h, p, cfg,
                                                  chunk=cfg.ssm_chunk)

            def g_body(h, gp):
                h, mc = jax.lax.scan(m_body, h, gp["m"])
                h, sc = blocks.slstm_block_prefill(h, gp["s"], cfg)
                return h, {"m": mc, "s": sc}
            x, cache = jax.lax.scan(g_body, x, params["stack"])
        elif fam == "zamba":
            shared = params["stack"]["shared"]

            def m_body(h, p):
                return blocks.mamba_block_prefill(h, p, cfg,
                                                  chunk=cfg.ssm_chunk)

            def g_body(h, gp):
                h, mc = jax.lax.scan(m_body, h, gp)
                h, sc = blocks.tblock_prefill(h, shared, cfg, cache_len)
                return h, {"mamba": mc, "shared": sc}
            x, cache_t = jax.lax.scan(g_body, x, params["stack"]["mamba"])
            cache = {"mamba": cache_t["mamba"], "shared": cache_t["shared"]}
        else:
            raise ValueError(fam)

        x = layers.apply_norm(x[:, -1:], params["ln_f"], cfg.norm)
        return layers.logits(x, params["unembed"])[:, 0], cache

    # -- decode ------------------------------------------------------------------
    def decode_step(self, params, cache, batch, pos):
        """batch["tokens"]: [B, 1]; pos: scalar int32, or ``[B]`` int32 for
        per-row positions (the gateway's continuous batch; recurrent
        SSM/xLSTM blocks ignore pos, attention blocks broadcast it)."""
        cfg = self.cfg
        fam = cfg.family
        self._check_servable()
        x = layers.embed(batch["tokens"], params["embed"]).astype(cfg.c_dtype)

        if fam in ("dense", "moe"):
            # the stacked cache rides in the carry and each layer writes one
            # position per row into it in place; as xs/ys it would be copied
            # slice by slice into a new stack, and that stack out again
            def body(carry, p):
                h, i, kc, vc = carry
                q, k, v = blocks.tblock_decode_project(h, p, cfg, pos)
                kc, vc = attention.cache_update(
                    kc, vc, k, v, pos, mode=cfg.cache_update, layer=i)
                h = blocks.tblock_decode_attend(
                    h, p, cfg, q, jax.lax.dynamic_index_in_dim(kc, i, 0, False),
                    jax.lax.dynamic_index_in_dim(vc, i, 0, False), pos)
                return (h, i + 1, kc, vc), None
            (x, _, k, v), _ = jax.lax.scan(
                body, (x, jnp.int32(0), cache["k"], cache["v"]),
                params["stack"])
            cache = {"k": k, "v": v}
        elif fam == "xlstm":
            def m_body(h, pc):
                p, c = pc
                h, c2 = blocks.mlstm_block_decode(h, p, cfg, c, pos)
                return h, c2

            def g_body(h, gpc):
                gp, gc = gpc
                h, mc = jax.lax.scan(m_body, h, (gp["m"], gc["m"]))
                h, sc = blocks.slstm_block_decode(h, gp["s"], cfg, gc["s"], pos)
                return h, {"m": mc, "s": sc}
            x, cache = jax.lax.scan(g_body, x, (params["stack"], cache))
        elif fam == "zamba":
            shared = params["stack"]["shared"]

            def m_body(h, pc):
                p, c = pc
                h, c2 = blocks.mamba_block_decode(h, p, cfg, c, pos)
                return h, c2

            def g_body(h, gpc):
                gp, gc = gpc
                h, mc = jax.lax.scan(m_body, h, (gp, gc["mamba"]))
                h, sc = blocks.tblock_decode(h, shared, cfg, gc["shared"], pos)
                return h, {"mamba": mc, "shared": sc}
            x, cache = jax.lax.scan(
                g_body, x, (params["stack"]["mamba"], cache))
        else:
            raise ValueError(fam)

        x = layers.apply_norm(x, params["ln_f"], cfg.norm)
        return layers.logits(x, params["unembed"])[:, 0], cache


# ===========================================================================
# Encoder-decoder (whisper-style; frontend is a stub: precomputed frames)
# ===========================================================================
class EncDec(_Recorder):
    def __init__(self, cfg):
        self.cfg = cfg

    def specs(self) -> dict:
        cfg = self.cfg
        sp = {
            "embed": layers.embed_specs(cfg.vocab, cfg.d_model),
            "pos_dec": ParamSpec((cfg.max_dec_len, cfg.d_model),
                                 (None, "embed"), init="scaled", scale=0.01),
            "enc_stack": stack_specs(blocks.tblock_specs(cfg),
                                     cfg.n_enc_layers),
            "ln_enc": layers.norm_specs(cfg.d_model, cfg.norm),
            "dec_stack": stack_specs(blocks.tblock_specs(cfg, cross=True),
                                     cfg.n_layers),
            "ln_f": layers.norm_specs(cfg.d_model, cfg.norm),
            "unembed": layers.unembed_specs(cfg.d_model, cfg.vocab),
        }
        sp = jax.tree.map(
            lambda s: dataclasses.replace(s, dtype=cfg.p_dtype)
            if jnp.issubdtype(s.dtype, jnp.floating) else s,
            sp, is_leaf=lambda x: isinstance(x, ParamSpec))
        return sp

    def encode(self, params, frames):
        """frames: [B, S_enc, D] stub frontend output."""
        cfg = self.cfg
        x = frames.astype(cfg.c_dtype)
        x = x + layers.sinusoidal_embedding(x.shape[1], cfg.d_model
                                            ).astype(cfg.c_dtype)[None]

        def body(h, p):
            h, _ = blocks.tblock_apply(h, p, cfg, causal=False)
            return h, None
        x, _ = jax.lax.scan(_maybe_remat(body, cfg.remat), x,
                                 params["enc_stack"])
        return layers.apply_norm(x, params["ln_enc"], cfg.norm)

    def _dec_embed(self, params, tokens, pos0=0):
        cfg = self.cfg
        x = layers.embed(tokens, params["embed"]).astype(cfg.c_dtype)
        pe = jax.lax.dynamic_slice_in_dim(params["pos_dec"], pos0,
                                          tokens.shape[1], axis=0)
        return x + pe.astype(cfg.c_dtype)[None]

    def apply(self, params, batch):
        """batch: frames [B,S_enc,D], tokens/labels [B,S_dec]."""
        cfg = self.cfg
        enc = self.encode(params, batch["frames"])
        x = self._dec_embed(params, batch["tokens"])

        def body(h, p):
            # per-layer cross K/V from encoder output
            ck = jnp.einsum("bsd,dhk->bshk", enc,
                            p["cross"]["wk"].astype(enc.dtype))
            cv = jnp.einsum("bsd,dhk->bshk", enc,
                            p["cross"]["wv"].astype(enc.dtype))
            h, _ = blocks.tblock_apply(h, p, cfg, enc_kv=(ck, cv))
            return h, None
        x, _ = jax.lax.scan(_maybe_remat(body, cfg.remat), x,
                                 params["dec_stack"])
        x = layers.apply_norm(x, params["ln_f"], cfg.norm)
        return layers.logits(x, params["unembed"]), jnp.zeros((), jnp.float32)

    def loss(self, params, batch):
        lg, _ = self.apply(params, batch)
        return layers.softmax_xent(lg, batch["labels"], batch.get("mask"))

    def cache_specs(self, batch: int, cache_len: int) -> dict:
        cfg = self.cfg
        sp = blocks.kv_cache_specs(cfg, batch, cache_len,
                                   prefix=(cfg.n_layers,))
        cross = blocks.kv_cache_specs(cfg, batch, cfg.enc_frames,
                                      prefix=(cfg.n_layers,))
        sp["ck"], sp["cv"] = cross["k"], cross["v"]
        return sp

    def prefill(self, params, batch, cache_len: int):
        cfg = self.cfg
        enc = self.encode(params, batch["frames"])
        x = self._dec_embed(params, batch["tokens"])

        def body(h, p):
            ck = jnp.einsum("bsd,dhk->bshk", enc,
                            p["cross"]["wk"].astype(enc.dtype))
            cv = jnp.einsum("bsd,dhk->bshk", enc,
                            p["cross"]["wv"].astype(enc.dtype))
            h, c = blocks.tblock_prefill(h, p, cfg, cache_len,
                                         enc_kv=(ck, cv))
            return h, c
        x, cache = jax.lax.scan(body, x, params["dec_stack"])
        x = layers.apply_norm(x[:, -1:], params["ln_f"], cfg.norm)
        return layers.logits(x, params["unembed"])[:, 0], cache

    def decode_step(self, params, cache, batch, pos):
        cfg = self.cfg
        x = self._dec_embed(params, batch["tokens"], pos0=pos)

        def body(h, pc):
            p, c = pc
            h, c2 = blocks.tblock_decode(h, p, cfg, c, pos,
                                         enc_kv=(c["ck"], c["cv"]))
            c2["ck"], c2["cv"] = c["ck"], c["cv"]
            return h, c2
        x, cache = jax.lax.scan(body, x, (params["dec_stack"], cache))
        x = layers.apply_norm(x, params["ln_f"], cfg.norm)
        return layers.logits(x, params["unembed"])[:, 0], cache


def build_model(cfg):
    return EncDec(cfg) if cfg.family == "encdec" else LM(cfg)
