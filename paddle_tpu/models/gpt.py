"""GPT-style causal decoder.

No direct reference counterpart (the reference's generative path is the
seq2seq machine-translation book model); included because causal LM is the
canonical long-context workload for the sequence-parallel / ring-attention
path (SURVEY.md §5 "long-context" gap) and exercises the Pallas causal
flash-attention kernel.
"""

import dataclasses

import jax
import jax.numpy as jnp

from .. import nn
from ..nn import functional as F


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    max_seq_len: int = 1024
    dropout: float = 0.0
    dtype: str = "float32"
    # > 0: stream the CE over vocab chunks of this size (must divide
    # vocab_size) so the full [B, S, V] logits never persist to the
    # backward — the chunk recomputes under jax.checkpoint. Trades
    # one extra logits matmul pass for ~2x less logits HBM traffic;
    # worthwhile at 32k+ vocabs on HBM-bound configs.
    # streaming vocab-chunked CE: a MEMORY lever (keeps the [B,S,V]
    # logits out of the residual set), NOT a speed lever: it pays one
    # more logits matmul, so where the logits fit, the plain fused CE
    # is the one to use; engage only for long-seq x huge-vocab configs.
    # No cell of the benchmark sets it (ROADMAP.md, Design).
    ce_vocab_chunk: int = 0
    # MoE (0 = dense FFN): experts shard over the mesh's "ep" axis via
    # distributed.sharded.gpt_rules; router aux loss folds into .loss()
    num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01


class MoEFFN(nn.Layer):
    """Mixture-of-experts FFN block (capability beyond the reference —
    SURVEY §2.3 expert parallel: NO). Wraps distributed.moe.moe_ffn with
    layer-managed parameters; expert-major weights [E, ...] shard over
    the "ep" mesh axis under the gpt_rules moe entries."""

    def __init__(self, hidden, num_experts, top_k=2, capacity_factor=1.25,
                 dtype="float32"):
        super().__init__(dtype=dtype)
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.wg = self.create_parameter([hidden, num_experts])
        self.w1 = self.create_parameter([num_experts, hidden, 4 * hidden])
        self.w2 = self.create_parameter([num_experts, 4 * hidden, hidden])
        self.last_aux_loss = 0.0

    def forward(self, x):
        from ..distributed.moe import moe_ffn

        params = {"wg": F._val(self.wg), "w1": F._val(self.w1),
                  "w2": F._val(self.w2)}
        y, aux = moe_ffn(params, x, k=self.top_k,
                         capacity_factor=self.capacity_factor)
        # same-trace stash: .loss() reads it within one jit trace
        self.last_aux_loss = aux
        return y


class GPTBlock(nn.Layer):
    def __init__(self, cfg):
        super().__init__(dtype=cfg.dtype)
        self.norm1 = nn.LayerNorm(cfg.hidden_size, dtype=cfg.dtype)
        self.attn = nn.MultiHeadAttention(cfg.hidden_size, cfg.num_heads,
                                          dropout=cfg.dropout,
                                          dtype=cfg.dtype)
        self.norm2 = nn.LayerNorm(cfg.hidden_size, dtype=cfg.dtype)
        if cfg.num_experts > 0:
            self.moe = MoEFFN(cfg.hidden_size, cfg.num_experts,
                              top_k=cfg.moe_top_k,
                              capacity_factor=cfg.moe_capacity_factor,
                              dtype=cfg.dtype)
        else:
            self.fc1 = nn.Linear(cfg.hidden_size, 4 * cfg.hidden_size,
                                 act="gelu", dtype=cfg.dtype)
            self.fc2 = nn.Linear(4 * cfg.hidden_size, cfg.hidden_size,
                                 dtype=cfg.dtype)
        self.drop = nn.Dropout(cfg.dropout)
        self._moe = cfg.num_experts > 0

    def forward(self, x):
        x = x + self.attn(self.norm1(x), is_causal=True)
        h = self.norm2(x)
        ff = self.moe(h) if self._moe else self.fc2(self.fc1(h))
        return x + self.drop(ff)


class GPT(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        self.wte = nn.Embedding([cfg.vocab_size, cfg.hidden_size],
                                dtype=cfg.dtype)
        self.wpe = nn.Embedding([cfg.max_seq_len, cfg.hidden_size],
                                dtype=cfg.dtype)
        self.drop = nn.Dropout(cfg.dropout)
        self.blocks = nn.LayerList([GPTBlock(cfg)
                                    for _ in range(cfg.num_layers)])
        self.norm_f = nn.LayerNorm(cfg.hidden_size, dtype=cfg.dtype)

    def forward(self, input_ids):
        x = self._final_hidden(input_ids)
        return jnp.einsum("bsh,vh->bsv", x, F._val(self.wte.weight))

    def loss(self, input_ids, labels):
        # fused CE: per-token logsumexp minus the gathered label logit.
        # Materialising log_softmax over [B, S, V] in fp32 costs ~4x the
        # logits' HBM footprint; the reduction form lets XLA fuse the fp32
        # upcast into the logsumexp and touch the full logits once.
        if self.cfg.ce_vocab_chunk > 0:
            h = self._final_hidden(input_ids)
            ce = streaming_softmax_ce(h, F._val(self.wte.weight), labels,
                                      self.cfg.ce_vocab_chunk)
        else:
            logits = self.forward(input_ids)
            lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
            lab = jnp.take_along_axis(logits, labels[..., None],
                                      axis=-1)[..., 0]
            ce = (lse - lab.astype(jnp.float32)).mean()
        if self.cfg.num_experts > 0:
            # router load-balance loss from the SAME trace's forward
            aux = sum(blk.moe.last_aux_loss for blk in self.blocks)
            ce = ce + self.cfg.moe_aux_weight * aux
        return ce

    def _final_hidden(self, input_ids):
        """forward() up to (and including) the final layer norm, without
        the head matmul."""
        seq = input_ids.shape[1]
        if seq > self.cfg.max_seq_len:
            raise ValueError(
                f"sequence length {seq} exceeds max_seq_len "
                f"{self.cfg.max_seq_len}")
        pos = jnp.arange(seq, dtype=jnp.int32)[None, :]
        x = self.drop(self.wte(input_ids) + self.wpe(pos))
        for blk in self.blocks:
            x = blk(x)
        return self.norm_f(x)


def streaming_softmax_ce(h, wte, labels, chunk):
    """Fused CE streamed over vocab chunks: mean(lse - z_label) where
    z = h @ wte^T, computed chunk-by-chunk with an online logsumexp so
    the [N, V] logits never exist at once — and jax.checkpoint on the
    chunk body keeps them out of the BACKWARD's residuals too (each
    chunk's logits recompute from h and its wte rows).

    h: [B, S, H] (or [N, H]); wte: [V, H]; labels int [B, S] / [N]."""
    v, hidden = wte.shape
    if v % chunk != 0:
        raise ValueError(f"ce_vocab_chunk {chunk} must divide vocab {v}")
    n_chunks = v // chunk
    hs = h.reshape(-1, hidden)
    lab = labels.reshape(-1)
    n = hs.shape[0]
    wcs = wte.reshape(n_chunks, chunk, hidden)
    bases = jnp.arange(n_chunks, dtype=jnp.int32) * chunk

    @jax.checkpoint
    def body(carry, xs):
        m, s, zlab = carry
        wc, base = xs
        z = jnp.einsum("nh,ch->nc", hs, wc,
                       preferred_element_type=jnp.float32)
        m_new = jnp.maximum(m, z.max(axis=-1))
        s = s * jnp.exp(m - m_new) + jnp.exp(
            z - m_new[:, None]).sum(axis=-1)
        in_c = (lab >= base) & (lab < base + chunk)
        zl = jnp.take_along_axis(
            z, jnp.clip(lab - base, 0, chunk - 1)[:, None], axis=1)[:, 0]
        zlab = jnp.where(in_c, zl, zlab)
        return (m_new, s, zlab), None

    init = (jnp.full((n,), -jnp.inf, jnp.float32),
            jnp.zeros((n,), jnp.float32),
            jnp.zeros((n,), jnp.float32))
    (m, s, zlab), _ = jax.lax.scan(body, init, (wcs, bases))
    return (m + jnp.log(s) - zlab).mean()
