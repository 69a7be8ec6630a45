"""BERT4Rec (arXiv:1904.06690): bidirectional self-attention over item
sequences with a masked-item (Cloze) objective.

Production-scale choices for a 10^6-item catalog:
* training uses sampled softmax over the masked positions (gold + shared
  negatives with logQ correction) - a [B,M,V] logits tensor at V=10^6 is
  not materializable;
* serving never materializes [B, V] scores either: scoring is a chunked
  top-k scan over the item-embedding table (``chunked_topk_scores``),
  which is also the retrieval_cand path (1 query x 1M candidates).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

from .common import normal_init
from .layers import layer_norm

PyTree = Any


@dataclasses.dataclass(frozen=True)
class Bert4RecConfig:
    name: str
    n_items: int = 1_000_000     # catalog size (retrieval_cand = 1M)
    d_model: int = 64
    n_blocks: int = 2
    n_heads: int = 2
    seq_len: int = 200
    d_ff: int = 256
    n_masked: int = 20           # masked positions per sequence
    n_negatives: int = 1024      # shared sampled-softmax negatives
    topk: int = 100
    v_chunk: int = 65536         # scoring chunk over the catalog
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32

    @property
    def vocab(self) -> int:
        return self.n_items + 2  # 0 = PAD, n_items+1 = MASK

    @property
    def mask_id(self) -> int:
        return self.n_items + 1


def init_params(rng, cfg: Bert4RecConfig) -> PyTree:
    d = cfg.d_model
    keys = iter(jax.random.split(rng, 8))
    params: Dict[str, Any] = {
        "item_emb": normal_init(next(keys), (cfg.vocab, d), 0.02,
                                cfg.param_dtype),
        "pos_emb": normal_init(next(keys), (cfg.seq_len, d), 0.02,
                               cfg.param_dtype),
        "ln_f_w": jnp.ones((d,), cfg.param_dtype),
        "ln_f_b": jnp.zeros((d,), cfg.param_dtype),
        "out_bias": jnp.zeros((), cfg.param_dtype),
    }
    n = cfg.n_blocks
    params["blocks"] = {
        "wqkv": normal_init(next(keys), (n, d, 3 * d), d ** -0.5,
                            cfg.param_dtype),
        "wo": normal_init(next(keys), (n, d, d), d ** -0.5,
                          cfg.param_dtype),
        "ln1_w": jnp.ones((n, d), cfg.param_dtype),
        "ln1_b": jnp.zeros((n, d), cfg.param_dtype),
        "ln2_w": jnp.ones((n, d), cfg.param_dtype),
        "ln2_b": jnp.zeros((n, d), cfg.param_dtype),
        "w1": normal_init(next(keys), (n, d, cfg.d_ff), d ** -0.5,
                          cfg.param_dtype),
        "b1": jnp.zeros((n, cfg.d_ff), cfg.param_dtype),
        "w2": normal_init(next(keys), (n, cfg.d_ff, d),
                          cfg.d_ff ** -0.5, cfg.param_dtype),
        "b2": jnp.zeros((n, d), cfg.param_dtype),
    }
    return params


def encode(params, seq, cfg: Bert4RecConfig):
    """seq [B,S] item ids (0=PAD) -> hidden [B,S,D]."""
    b, s = seq.shape
    x = params["item_emb"][seq].astype(cfg.compute_dtype)
    x = x + params["pos_emb"][None, :s].astype(cfg.compute_dtype)
    pad = seq == 0  # [B,S]
    h = cfg.n_heads
    dh = cfg.d_model // h

    def block(x, bp):
        bp = jax.tree.map(lambda p: p.astype(cfg.compute_dtype), bp)
        y = layer_norm(x, bp["ln1_w"], bp["ln1_b"])
        qkv = jnp.einsum("bsd,dk->bsk", y, bp["wqkv"])
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, s, h, dh)
        k = k.reshape(b, s, h, dh)
        v = v.reshape(b, s, h, dh)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(dh)
        sc = jnp.where(pad[:, None, None, :], -1e30, sc)
        p = jax.nn.softmax(sc.astype(jnp.float32), axis=-1).astype(x.dtype)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, s, cfg.d_model)
        x = x + jnp.einsum("bsd,dk->bsk", o, bp["wo"])
        y = layer_norm(x, bp["ln2_w"], bp["ln2_b"])
        y = jax.nn.gelu(jnp.einsum("bsd,df->bsf", y, bp["w1"]) + bp["b1"])
        x = x + jnp.einsum("bsf,fd->bsd", y, bp["w2"]) + bp["b2"]
        return x, None

    x, _ = jax.lax.scan(block, x, params["blocks"])
    return layer_norm(x, params["ln_f_w"].astype(cfg.compute_dtype),
                      params["ln_f_b"].astype(cfg.compute_dtype))


def masked_item_loss(params, batch, cfg: Bert4RecConfig):
    """batch: seq [B,S] (with MASK tokens already placed),
    masked_pos [B,M], masked_ids [B,M], negatives [K] shared ids."""
    hidden = encode(params, batch["seq"], cfg)  # [B,S,D]
    hm = jnp.take_along_axis(
        hidden, batch["masked_pos"][..., None], axis=1
    )  # [B,M,D]
    emb = params["item_emb"].astype(cfg.compute_dtype)
    gold_e = emb[batch["masked_ids"]]            # [B,M,D]
    neg_e = emb[batch["negatives"]]              # [K,D]
    gold_logit = jnp.sum(hm * gold_e, -1, dtype=jnp.float32)  # [B,M]
    neg_logit = jnp.einsum("bmd,kd->bmk", hm, neg_e).astype(jnp.float32)
    # sampled softmax: gold vs negatives (uniform logQ cancels up to gold)
    logits = jnp.concatenate([gold_logit[..., None], neg_logit], -1)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    nll = lse - gold_logit
    valid = batch["masked_ids"] > 0
    return jnp.sum(nll * valid) / jnp.maximum(valid.sum(), 1)


def chunked_topk_scores(params, query, cfg: Bert4RecConfig):
    """query [B,D] -> (top-k scores [B,k], ids [B,k]) without a [B,V]
    intermediate: lax.scan over catalog chunks with a running top-k."""
    k = cfg.topk
    v = cfg.n_items + 1  # score real items 1..n_items (skip PAD row 0)
    chunk = cfg.v_chunk
    n_chunks = -(-v // chunk)
    vpad = n_chunks * chunk
    emb = params["item_emb"].astype(cfg.compute_dtype)
    emb = jnp.pad(emb[:v], ((0, vpad - v), (0, 0)))
    b = query.shape[0]

    def body(carry, ci):
        best_s, best_i = carry
        tbl = jax.lax.dynamic_slice_in_dim(emb, ci * chunk, chunk, 0)
        sc = jnp.einsum("bd,cd->bc", query, tbl).astype(jnp.float32)
        ids = ci * chunk + jnp.arange(chunk)
        ids = jnp.broadcast_to(ids[None], (b, chunk))
        sc = jnp.where((ids >= 1) & (ids <= cfg.n_items), sc, -jnp.inf)
        cat_s = jnp.concatenate([best_s, sc], -1)
        cat_i = jnp.concatenate([best_i, ids], -1)
        s, idx = jax.lax.top_k(cat_s, k)
        return (s, jnp.take_along_axis(cat_i, idx, -1)), None

    init = (jnp.full((b, k), -jnp.inf, jnp.float32),
            jnp.zeros((b, k), jnp.int32))
    (s, i), _ = jax.lax.scan(body, init, jnp.arange(n_chunks))
    return s, i


def serve_scores(params, batch, cfg: Bert4RecConfig):
    """Next-item scoring: encode session, score last position vs catalog."""
    hidden = encode(params, batch["seq"], cfg)
    # last non-pad position per row
    lengths = jnp.sum((batch["seq"] > 0).astype(jnp.int32), -1)
    last = jnp.take_along_axis(
        hidden, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1
    )[:, 0]
    return chunked_topk_scores(params, last, cfg)


def make_sharded_serve(cfg: Bert4RecConfig, mesh, dp_axes):
    """shard_map scoring: each "model" shard scores only its local vocab
    shard and keeps a local top-k; the only cross-shard traffic is the
    [model, B, k] candidate merge (the pjit auto-sharded version
    all-gathers table chunks per scan step - measured collective-bound,
    see EXPERIMENTS.md §Perf/bert4rec)."""
    import numpy as np
    from jax.sharding import PartitionSpec as P

    tp = mesh.shape["model"]
    dp_dim = tuple(dp_axes) if len(dp_axes) > 1 else dp_axes[0]
    vocab = cfg.vocab
    assert vocab % tp == 0
    vshard = vocab // tp

    def local(params, seq):
        emb_local = params["item_emb"]  # [V/tp, D]
        # vocab-sharded embedding lookup: partial take + psum
        shard = jax.lax.axis_index("model")
        offset = shard * vshard
        ids = seq - offset
        ok = (ids >= 0) & (ids < vshard)
        rows = jnp.take(emb_local, jnp.clip(ids, 0, vshard - 1), axis=0)
        x = jnp.where(ok[..., None], rows, 0.0)
        x = jax.lax.psum(x, "model").astype(cfg.compute_dtype)

        # encoder on full (replicated-over-model) activations
        p_rep = {k: v for k, v in params.items() if k != "item_emb"}
        b, s = seq.shape
        x = x + p_rep["pos_emb"][None, :s].astype(cfg.compute_dtype)
        pad = seq == 0
        h, dh = cfg.n_heads, cfg.d_model // cfg.n_heads

        def block(x, bp):
            bp = jax.tree.map(lambda p: p.astype(cfg.compute_dtype), bp)
            y = layer_norm(x, bp["ln1_w"], bp["ln1_b"])
            qkv = jnp.einsum("bsd,dk->bsk", y, bp["wqkv"])
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(b, s, h, dh)
            k = k.reshape(b, s, h, dh)
            v = v.reshape(b, s, h, dh)
            sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(dh)
            sc = jnp.where(pad[:, None, None, :], -1e30, sc)
            pr = jax.nn.softmax(sc.astype(jnp.float32), -1).astype(x.dtype)
            o = jnp.einsum("bhqk,bkhd->bqhd", pr, v).reshape(
                b, s, cfg.d_model)
            x = x + jnp.einsum("bsd,dk->bsk", o, bp["wo"])
            y = layer_norm(x, bp["ln2_w"], bp["ln2_b"])
            y = jax.nn.gelu(jnp.einsum("bsd,df->bsf", y, bp["w1"])
                            + bp["b1"])
            x = x + jnp.einsum("bsf,fd->bsd", y, bp["w2"]) + bp["b2"]
            return x, None

        x, _ = jax.lax.scan(block, x, params["blocks"])
        x = layer_norm(x, p_rep["ln_f_w"].astype(cfg.compute_dtype),
                       p_rep["ln_f_b"].astype(cfg.compute_dtype))
        lengths = jnp.sum((seq > 0).astype(jnp.int32), -1)
        query = jnp.take_along_axis(
            x, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1)[:, 0]

        # local-vocab chunked top-k
        kk = cfg.topk
        chunk = min(cfg.v_chunk, vshard)
        n_chunks = -(-vshard // chunk)
        vpad = n_chunks * chunk
        tbl = jnp.pad(emb_local, ((0, vpad - vshard), (0, 0))).astype(
            cfg.compute_dtype)
        bq = query.shape[0]

        def body(carry, ci):
            bs, bi = carry
            t = jax.lax.dynamic_slice_in_dim(tbl, ci * chunk, chunk, 0)
            sc = jnp.einsum("bd,cd->bc", query, t).astype(jnp.float32)
            ids = offset + ci * chunk + jnp.arange(chunk)
            ids = jnp.broadcast_to(ids[None], (bq, chunk))
            sc = jnp.where((ids >= 1) & (ids <= cfg.n_items), sc, -jnp.inf)
            cs = jnp.concatenate([bs, sc], -1)
            cidx = jnp.concatenate([bi, ids], -1)
            s_, ix = jax.lax.top_k(cs, kk)
            return (s_, jnp.take_along_axis(cidx, ix, -1)), None

        init = (jnp.full((bq, kk), -jnp.inf, jnp.float32),
                jnp.zeros((bq, kk), jnp.int32))
        (ls, li), _ = jax.lax.scan(body, init, jnp.arange(n_chunks))

        # merge the tp local top-k lists (the only non-psum collective)
        all_s = jax.lax.all_gather(ls, "model")  # [tp, B, k]
        all_i = jax.lax.all_gather(li, "model")
        all_s = jnp.moveaxis(all_s, 0, 1).reshape(bq, tp * kk)
        all_i = jnp.moveaxis(all_i, 0, 1).reshape(bq, tp * kk)
        s_, ix = jax.lax.top_k(all_s, kk)
        return s_, jnp.take_along_axis(all_i, ix, -1)

    in_specs = (
        {
            "item_emb": P("model", None),
            "pos_emb": P(), "ln_f_w": P(), "ln_f_b": P(), "out_bias": P(),
            "blocks": jax.tree.map(lambda _: P(),
                                   {"wqkv": 0, "wo": 0, "ln1_w": 0,
                                    "ln1_b": 0, "ln2_w": 0, "ln2_b": 0,
                                    "w1": 0, "b1": 0, "w2": 0, "b2": 0}),
        },
        P(dp_dim, None),
    )
    fn = jax.shard_map(
        local, mesh=mesh, in_specs=in_specs,
        out_specs=(P(dp_dim, None), P(dp_dim, None)), check_vma=False,
    )

    def serve(params, batch):
        return fn(params, batch["seq"])

    return serve
