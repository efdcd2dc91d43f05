"""Composable decoder-only LM covering all 10 assigned architectures.

A model is a tiled ``(block, ffn)`` pattern (``ArchConfig.layer_pattern`` x
``ffn_pattern``) scanned over ``n_groups`` repeats, with optional unscanned
leading dense layers (``first_k_dense``, DeepSeekMoE).  Block kinds:

  attn   full causal GQA           (llama3, grok, qwen2, pixtral, musicgen, …)
  swa    sliding-window GQA        (h2o-danube; gemma2 local layers)
  mamba  selective SSM             (jamba)
  rwkv   RWKV6 time+channel mix    (rwkv6 — ffn kind "none")

FFN kinds: dense (GLU), moe (top-k capacity dispatch), none.

Three execution modes share one parameter tree:
  loss(params, batch)                — training objective (CE + MoE aux)
  prefill(params, batch)             — full-seq forward -> (last logits, cache)
  decode_step(params, tok, pos, cache) — one token against the cache

Partitioning is derived from logical axes (models/params.py) via the rule
sets below; the node-stacked decentralized training variant prepends the
node axis to every spec.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import params as pr
from repro.models.attention import (
    attention_decode,
    attention_forward,
    init_kv_cache,
    init_paged_kv,
    paged_attention_decode,
)
from repro.models.config import ArchConfig, ShapeConfig
from repro.models.layers import (
    chunked_logits_xent,
    embed,
    embedding_decl,
    glu_mlp,
    glu_mlp_decl,
    rmsnorm,
    rmsnorm_decl,
    weight_einsum,
)
from repro.models.moe import moe_decl, moe_ffn
from repro.models.ssm import (
    mamba_decl,
    mamba_forward,
    mamba_init_state,
    rwkv_decl,
    rwkv_forward,
    rwkv_decode,
    rwkv_init_state,
)

# -- sharding rule sets -------------------------------------------------------

def train_rules() -> dict:
    """Megatron-style tensor parallelism over the `model` axis."""
    return {
        "embed": None, "vocab": "model", "q_heads": "model",
        "kv_heads": "model", "mlp": "model", "hidden": "model",
        "experts": None, "state": None, "layers": None,
    }


def serve_rules() -> dict:
    """Inference: additionally shard the d_model dim over `data` (weight-
    gathered FSDP-style serving) so multi-100B models fit per chip."""
    r = train_rules()
    r["embed"] = "data"
    r["experts"] = "data"
    return r


def train_fsdp_rules() -> dict:
    """Hierarchical DR-DSGD (beyond paper): each node's replica is ALSO
    FSDP-sharded over an inner `fsdp` mesh axis, fixing the K x params
    memory blowup of naive decentralized training at multi-100B scale."""
    r = train_rules()
    r["embed"] = "fsdp"
    return r


# -- the model ----------------------------------------------------------------

def _layer_decl(cfg: ArchConfig, blk: str, ffn: str):
    d: dict[str, Any] = {"norm1": rmsnorm_decl(cfg.d_model)}
    if blk in ("attn", "swa"):
        from repro.models.attention import attention_decl

        d["mix"] = attention_decl(cfg)
    elif blk == "mamba":
        d["mix"] = mamba_decl(cfg)
    elif blk == "rwkv":
        d["mix"] = rwkv_decl(cfg)
    else:
        raise ValueError(f"unknown block kind {blk!r}")
    if ffn == "dense":
        d["norm2"] = rmsnorm_decl(cfg.d_model)
        d["ffn"] = glu_mlp_decl(cfg.d_model, cfg.d_ff)
    elif ffn == "moe":
        d["norm2"] = rmsnorm_decl(cfg.d_model)
        d["ffn"] = moe_decl(cfg)
    elif ffn != "none":
        raise ValueError(f"unknown ffn kind {ffn!r}")
    return d


def _stack_decls(decl, n: int):
    """Prepend a scanned (n_groups, …) 'layers' axis to every decl leaf."""
    return jax.tree.map(
        lambda d: pr.ParamDecl((n,) + d.shape, ("layers",) + d.axes,
                               d.init, d.scale, d.dtype),
        decl,
        is_leaf=lambda x: isinstance(x, pr.ParamDecl),
    )


@dataclasses.dataclass(frozen=True)
class TransformerLM:
    cfg: ArchConfig

    # -- parameters -----------------------------------------------------------

    def decl(self):
        cfg = self.cfg
        group = {
            f"l{i}": _layer_decl(cfg, blk, ffn)
            for i, (blk, ffn) in enumerate(cfg.group_pattern())
        }
        d = {
            "embedding": embedding_decl(cfg.vocab, cfg.d_model),
            "groups": _stack_decls(group, cfg.n_groups),
            "final_norm": rmsnorm_decl(cfg.d_model),
        }
        if cfg.first_k_dense:
            d["head_layers"] = {
                f"h{i}": _layer_decl(cfg, blk, ffn)
                for i, (blk, ffn) in enumerate(cfg.head_layers())
            }
        if not cfg.tie_embeddings:
            d["lm_head"] = {
                "table": pr.normal((cfg.vocab, cfg.d_model), ("vocab", "embed"),
                                   fan_in=cfg.d_model)
            }
        return d

    def init(self, key):
        return pr.init_tree(key, self.decl())

    def param_shapes(self):
        return pr.shape_tree(self.decl())

    def param_specs(self, mesh=None, mode: str = "train", node_axis=None):
        rules = {
            "train": train_rules,
            "serve": serve_rules,
            "train_fsdp": train_fsdp_rules,
        }[mode]()
        mesh_shape = dict(mesh.shape) if mesh is not None else None
        leading = (node_axis,) if node_axis is not None else ()
        return pr.spec_tree(self.decl(), rules, mesh_shape, leading=leading)

    def num_params(self) -> int:
        return pr.count_params(self.decl())

    def num_active_params(self) -> int:
        """Params touched per token (MoE: only top_k routed experts active)."""
        cfg = self.cfg
        total = self.num_params()
        if cfg.moe is None:
            return total
        n_moe = sum(1 for _, f in cfg._full_pattern() if f == "moe")
        per_expert = 3 * cfg.d_model * cfg.moe.d_expert
        routed = n_moe * cfg.moe.num_experts * per_expert
        active = n_moe * cfg.moe.top_k * per_expert
        return total - routed + active

    # -- embedding helpers ----------------------------------------------------

    def _unembed_table(self, params):
        # a tied model's tree may carry a head table of its own (the serving
        # engine's bfloat16 copy); the embedding gather keeps its table
        return params.get("lm_head", params["embedding"])["table"]

    def _input_embed(self, params, batch, drop_last_token: bool):
        """Returns (x (B,S,D), prefix_len). Stub frontends prepend embeddings."""
        cfg = self.cfg
        toks = batch["tokens"]
        if drop_last_token:
            toks = toks[:, :-1]
        x = embed(params["embedding"], toks, cfg.compute_dtype)
        if cfg.frontend == "token":
            return x, 0
        emb = batch["embeddings"].astype(cfg.compute_dtype)
        return jnp.concatenate([emb, x], axis=1), emb.shape[1]

    # -- layer application ----------------------------------------------------

    def _apply_layer_fwd(self, p, x, blk, ffn, positions, aux, state,
                         want_cache: bool):
        """Full-sequence path; returns (x, aux, new_cache_or_None)."""
        cfg = self.cfg
        h = rmsnorm(p["norm1"], x, cfg.rmsnorm_eps)
        new_cache = None
        if blk in ("attn", "swa"):
            if want_cache:
                out, kv = attention_forward(
                    p["mix"], h, cfg, kind=blk, positions=positions,
                    return_kv=True)
                window = cfg.sliding_window if blk == "swa" else None
                if window is not None and kv["k"].shape[1] > window:
                    kv = {k: v[:, -window:] for k, v in kv.items()}
                new_cache = kv
            else:
                out = attention_forward(p["mix"], h, cfg, kind=blk,
                                        positions=positions)
            x = x + out
        elif blk == "mamba":
            out, st = mamba_forward(p["mix"], h, cfg)
            x = x + out
            new_cache = st if want_cache else None
        elif blk == "rwkv":
            out, st = rwkv_forward(p["mix"], h, cfg)
            x = x + out
            new_cache = st if want_cache else None
        if ffn in ("dense", "moe"):
            h2 = rmsnorm(p["norm2"], x, cfg.rmsnorm_eps)
            if ffn == "dense":
                x = x + glu_mlp(p["ffn"], h2, cfg.compute_dtype).astype(x.dtype)
            else:
                out, moe_aux = moe_ffn(p["ffn"], h2, cfg)
                x = x + out
                aux = aux + moe_aux
        return x, aux, new_cache

    def _apply_layer_decode(self, p, x, blk, ffn, pos, cache, *,
                            tables=None, max_len=None):
        """One decode layer.  ``tables`` switches attn/swa layers onto the
        paged read/write path (``pos`` is then per-slot (B,) instead of a
        scalar); recurrent layers are per-slot rows either way."""
        cfg = self.cfg
        h = rmsnorm(p["norm1"], x, cfg.rmsnorm_eps)
        if blk in ("attn", "swa"):
            if tables is not None:
                out, new_cache = paged_attention_decode(
                    p["mix"], h, cfg, kind=blk, pool=cache,
                    table=tables[blk], pos=pos, max_len=max_len)
            else:
                out, new_cache = attention_decode(p["mix"], h, cfg, kind=blk,
                                                  cache=cache, pos=pos)
        elif blk == "mamba":
            out, new_cache = mamba_forward(p["mix"], h, cfg, cache)
        elif blk == "rwkv":
            out, new_cache = rwkv_decode(p["mix"], h, cfg, cache)
        x = x + out
        if ffn in ("dense", "moe"):
            h2 = rmsnorm(p["norm2"], x, cfg.rmsnorm_eps)
            if ffn == "dense":
                x = x + glu_mlp(p["ffn"], h2, cfg.compute_dtype).astype(x.dtype)
            else:
                out, _ = moe_ffn(p["ffn"], h2, cfg)
                x = x + out
        return x, new_cache

    # -- full-sequence forward (train / prefill) -------------------------------

    def _forward(self, params, batch, want_cache: bool, drop_last_token: bool):
        cfg = self.cfg
        x, prefix = self._input_embed(params, batch, drop_last_token)
        s = x.shape[1]
        positions = jnp.arange(s, dtype=jnp.int32)
        aux = jnp.zeros((), jnp.float32)
        pattern = cfg.group_pattern()
        head_caches = []
        for i, (blk, ffn) in enumerate(cfg.head_layers()):
            x, aux, c = self._apply_layer_fwd(
                params["head_layers"][f"h{i}"], x, blk, ffn, positions, aux,
                None, want_cache)
            head_caches.append(c)

        def group_body(carry, gp):
            x, aux = carry
            caches = {}
            for i, (blk, ffn) in enumerate(pattern):
                x, aux, c = self._apply_layer_fwd(
                    gp[f"l{i}"], x, blk, ffn, positions, aux, None, want_cache)
                caches[f"l{i}"] = c if want_cache else jnp.zeros((0,))
            return (x, aux), caches

        if cfg.remat and not want_cache:
            if cfg.remat_policy == "dots":
                body = jax.remat(
                    group_body,
                    policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
            else:
                body = jax.remat(group_body)
        else:
            body = group_body
        if cfg.scan_layers and cfg.n_groups > 1:
            (x, aux), group_caches = jax.lax.scan(
                body, (x, aux), params["groups"])
        else:
            # unscanned fallback (single group or debugging)
            gcs = []
            for gi in range(cfg.n_groups):
                gp = jax.tree.map(lambda a, g=gi: a[g], params["groups"])
                (x, aux), gc = body((x, aux), gp)
                gcs.append(gc)
            group_caches = jax.tree.map(lambda *xs: jnp.stack(xs), *gcs)
        x = rmsnorm(params["final_norm"], x, cfg.rmsnorm_eps)
        return x, aux, prefix, (head_caches, group_caches)

    # -- public API -----------------------------------------------------------

    def loss(self, params, batch):
        """Training objective: mean CE over text positions + MoE aux loss.

        batch: {"tokens": (B, S_txt+1) int32[, "embeddings": (B,P,D)]}.
        """
        cfg = self.cfg
        x, aux, prefix, _ = self._forward(
            params, batch, want_cache=False, drop_last_token=True)
        labels = batch["tokens"][:, 1:]
        h_txt = x[:, prefix:] if prefix else x
        table = self._unembed_table(params)
        ce = chunked_logits_xent(
            h_txt, table, labels, chunk=cfg.logits_chunk,
            logit_softcap_val=cfg.logit_softcap)
        return ce + aux

    def logits_all(self, params, batch):
        """Full logits over text positions (small models / eval only)."""
        cfg = self.cfg
        x, _, prefix, _ = self._forward(params, batch, False, False)
        h_txt = x[:, prefix:] if prefix else x
        table = self._unembed_table(params)
        logits = jnp.einsum("bsd,vd->bsv", h_txt.astype(jnp.float32),
                            table.astype(jnp.float32))
        if cfg.logit_softcap:
            logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
        return logits

    def prefill(self, params, batch):
        """Forward the whole prompt; returns (last-position logits, cache)."""
        cfg = self.cfg
        x, _, prefix, caches = self._forward(params, batch, True, False)
        table = self._unembed_table(params)
        last = weight_einsum("bd,vd->bv", x[:, -1], table, jnp.float32)
        if cfg.logit_softcap:
            last = cfg.logit_softcap * jnp.tanh(last / cfg.logit_softcap)
        return last, caches

    def init_cache(self, batch: int, seq_len: int):
        """Zeroed decode cache for (batch, seq_len) context."""
        cfg = self.cfg

        def layer_cache(blk):
            if blk in ("attn", "swa"):
                return init_kv_cache(cfg, batch, seq_len, blk)
            if blk == "mamba":
                return mamba_init_state(cfg, batch)
            if blk == "rwkv":
                return rwkv_init_state(cfg, batch)
            raise ValueError(blk)

        head = [layer_cache(blk) for blk, _ in cfg.head_layers()]
        group = {
            f"l{i}": jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (cfg.n_groups,) + x.shape),
                layer_cache(blk))
            for i, (blk, _) in enumerate(cfg.group_pattern())
        }
        return {"head": head, "groups": group}

    def cache_pspecs(self, batch: int, seq_len: int, mesh, data_axes,
                     model_axis: str = "model"):
        """PartitionSpecs for the decode cache.

        Batch is sharded over ``data_axes`` when divisible; for batch=1
        (long_500k) the KV-cache *sequence* axis is sharded instead (XLA
        inserts the partial-softmax reductions). Head/feature dims shard over
        the model axis when divisible.
        """
        cfg = self.cfg
        mesh_shape = dict(mesh.shape)
        dsize = 1
        for a in (data_axes if isinstance(data_axes, tuple) else (data_axes,)):
            dsize *= mesh_shape[a]
        msize = mesh_shape[model_axis]

        def b_ax(b):
            return data_axes if b % dsize == 0 else None

        def m_ax(n):
            return model_axis if n % msize == 0 else None

        def kv_spec(kind):
            t = seq_len
            if kind == "swa" and cfg.sliding_window is not None:
                t = min(t, cfg.sliding_window)
            bspec = b_ax(batch)
            # batch=1: shard the sequence axis over data instead
            sspec = None if bspec is not None else (
                data_axes if t % dsize == 0 else None)
            kvs = P(bspec, sspec, m_ax(cfg.n_kv_heads), None)
            return {"k": kvs, "v": kvs}

        def layer_spec(blk):
            if blk in ("attn", "swa"):
                return kv_spec(blk)
            if blk == "mamba":
                di = cfg.mamba_expand * cfg.d_model
                return {
                    "conv": P(b_ax(batch), None, m_ax(di)),
                    "ssm": P(b_ax(batch), m_ax(di), None),
                }
            if blk == "rwkv":
                h = cfg.d_model // cfg.rwkv_head_dim
                return {
                    "x_time": P(b_ax(batch), None),
                    "x_chan": P(b_ax(batch), None),
                    "wkv": P(b_ax(batch), m_ax(h), None, None),
                }
            raise ValueError(blk)

        def stack(spec_tree):
            return jax.tree.map(
                lambda s: P(None, *s), spec_tree,
                is_leaf=lambda x: isinstance(x, P))

        head = [layer_spec(blk) for blk, _ in cfg.head_layers()]
        group = {
            f"l{i}": stack(layer_spec(blk))
            for i, (blk, _) in enumerate(cfg.group_pattern())
        }
        return {"head": head, "groups": group}

    def init_paged_cache(self, batch: int, num_pages: dict, page_size: int,
                         *, quantized: bool):
        """Paged decode cache: attn/swa layers become shared page pools
        (``num_pages`` per layer, keyed by block kind), recurrent layers
        stay per-slot (batch, ...) rows.  Structure mirrors
        :meth:`init_cache` so the group scan carries it unchanged.
        """
        cfg = self.cfg

        def layer_cache(blk):
            if blk in ("attn", "swa"):
                return init_paged_kv(cfg, num_pages[blk], page_size,
                                     quantized=quantized)
            if blk == "mamba":
                return mamba_init_state(cfg, batch)
            if blk == "rwkv":
                return rwkv_init_state(cfg, batch)
            raise ValueError(blk)

        head = [layer_cache(blk) for blk, _ in cfg.head_layers()]
        group = {
            f"l{i}": jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (cfg.n_groups,) + x.shape),
                layer_cache(blk))
            for i, (blk, _) in enumerate(cfg.group_pattern())
        }
        return {"head": head, "groups": group}

    def decode_step(self, params, token, pos, cache):
        """One decode step. token: (B,1) int32; pos: scalar int32.

        Returns (logits (B, vocab), new_cache).
        """
        return self._decode_common(params, token, pos, cache)

    def paged_decode_step(self, params, token, pos, cache, tables, *,
                          max_len: int):
        """One decode step against a paged cache (:meth:`init_paged_cache`).

        token: (B, 1) int32; pos: (B,) int32 per-slot positions; tables:
        {kind: (B, n_blocks) int32} traced block tables.  ``max_len`` is the
        logical ring length of full-attention layers (static).
        """
        return self._decode_common(params, token, pos, cache,
                                   tables=tables, max_len=max_len)

    def _decode_common(self, params, token, pos, cache, tables=None,
                       max_len=None):
        cfg = self.cfg
        x = embed(params["embedding"], token, cfg.compute_dtype)
        pattern = cfg.group_pattern()
        new_head = []
        for i, (blk, ffn) in enumerate(cfg.head_layers()):
            x, c = self._apply_layer_decode(
                params["head_layers"][f"h{i}"], x, blk, ffn, pos,
                cache["head"][i], tables=tables, max_len=max_len)
            new_head.append(c)

        def group_body(x, inp):
            gp, gc = inp
            new_gc = {}
            for i, (blk, ffn) in enumerate(pattern):
                x, c = self._apply_layer_decode(
                    gp[f"l{i}"], x, blk, ffn, pos, gc[f"l{i}"],
                    tables=tables, max_len=max_len)
                new_gc[f"l{i}"] = c
            return x, new_gc

        if cfg.scan_layers and cfg.n_groups > 1:
            x, new_groups = jax.lax.scan(
                group_body, x, (params["groups"], cache["groups"]))
        else:
            ngs = []
            for gi in range(cfg.n_groups):
                gp = jax.tree.map(lambda a: a[gi], params["groups"])
                gc = jax.tree.map(lambda a: a[gi], cache["groups"])
                x, ng = group_body(x, (gp, gc))
                ngs.append(ng)
            new_groups = jax.tree.map(lambda *xs: jnp.stack(xs), *ngs)
        x = rmsnorm(params["final_norm"], x, cfg.rmsnorm_eps)
        table = self._unembed_table(params)
        logits = weight_einsum("bd,vd->bv", x[:, 0], table, jnp.float32)
        if cfg.logit_softcap:
            logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
        return logits, {"head": new_head, "groups": new_groups}


# -- input specs for the dry-run ---------------------------------------------

def input_shapes(cfg: ArchConfig, shape: ShapeConfig, num_nodes: int | None = None
                 ) -> dict:
    """ShapeDtypeStruct stand-ins for each execution mode (no allocation).

    train:   node-stacked batch {"tokens": (K, B/K, S_txt+1)[, "embeddings"]}
    prefill: {"tokens": (B, S_txt)[, "embeddings": (B, P, D)]}
    decode:  {"token": (B,1), "pos": scalar}  (cache built separately)
    """
    f = jax.ShapeDtypeStruct
    s, b = shape.seq_len, shape.global_batch
    prefix = cfg.frontend_len if cfg.frontend != "token" else 0

    def batch_dims(batch):
        if shape.kind == "train":
            k = num_nodes
            return (k, batch // k)
        return (batch,)

    bd = batch_dims(b)
    if shape.kind in ("train", "prefill"):
        s_txt = s - prefix
        extra = 1 if shape.kind == "train" else 0
        out = {"tokens": f(bd + (s_txt + extra,), jnp.int32)}
        if prefix:
            out["embeddings"] = f(bd + (prefix, cfg.d_model), cfg.compute_dtype)
        return out
    return {
        "token": f(bd + (1,), jnp.int32),
        "pos": f((), jnp.int32),
    }


# Task-spec name: ShapeDtypeStruct stand-ins for every model input.
input_specs = input_shapes
