"""BERT — the flagship transformer model.

Parity targets: the reference's collective-training BERT path (SURVEY.md
§3.3 — the "BERT/ResNet cluster path") and the fused-attention transformer
benchmark config; fused attention replaces
/root/reference/paddle/fluid/operators/fused/multihead_matmul_op.cu and
math/bert_encoder_functor.cu with the Pallas flash-attention kernel
(paddle_tpu/kernels/flash_attention.py).

TPU-first design:
- bfloat16 activations by default (MXU-native), fp32 layernorm statistics.
- static shapes everywhere; padding masks, not ragged LoD.
- parameter names are stable, so parallel.sharding_rules can map them to
  megatron-style PartitionSpecs (tp axis on qkv/ffn matmuls).
"""

import dataclasses

import jax.numpy as jnp

from .. import nn
from ..nn import functional as F


@dataclasses.dataclass
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    hidden_dropout_prob: float = 0.1
    attention_probs_dropout_prob: float = 0.1
    layer_norm_eps: float = 1e-12
    dtype: str = "float32"


def bert_base_config(**kw):
    return BertConfig(**kw)


def bert_tiny_config(**kw):
    base = dict(vocab_size=1024, hidden_size=64, num_hidden_layers=2,
                num_attention_heads=4, intermediate_size=128,
                max_position_embeddings=128)
    base.update(kw)
    return BertConfig(**base)


class BertEmbeddings(nn.Layer):
    def __init__(self, cfg):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        self.word_embeddings = nn.Embedding(
            [cfg.vocab_size, cfg.hidden_size], dtype=cfg.dtype)
        self.position_embeddings = nn.Embedding(
            [cfg.max_position_embeddings, cfg.hidden_size], dtype=cfg.dtype)
        self.token_type_embeddings = nn.Embedding(
            [cfg.type_vocab_size, cfg.hidden_size], dtype=cfg.dtype)
        self.layer_norm = nn.LayerNorm(cfg.hidden_size,
                                       epsilon=cfg.layer_norm_eps,
                                       dtype=cfg.dtype)
        self.dropout = nn.Dropout(cfg.hidden_dropout_prob)

    def forward(self, input_ids, token_type_ids=None):
        seq = input_ids.shape[1]
        if seq > self.cfg.max_position_embeddings:
            raise ValueError(
                f"sequence length {seq} exceeds max_position_embeddings "
                f"{self.cfg.max_position_embeddings}")
        pos = jnp.arange(seq, dtype=jnp.int32)[None, :]
        emb = self.word_embeddings(input_ids)
        emb = emb + self.position_embeddings(pos)
        if token_type_ids is None:
            token_type_ids = jnp.zeros_like(input_ids)
        emb = emb + self.token_type_embeddings(token_type_ids)
        return self.dropout(self.layer_norm(emb))


class BertModel(nn.Layer):
    def __init__(self, cfg: BertConfig):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        self.embeddings = BertEmbeddings(cfg)
        self.encoder = nn.TransformerEncoder(
            lambda: nn.TransformerEncoderLayer(
                cfg.hidden_size, cfg.num_attention_heads,
                cfg.intermediate_size, dropout=cfg.hidden_dropout_prob,
                activation="gelu", dtype=cfg.dtype),
            cfg.num_hidden_layers)
        self.pooler = nn.Linear(cfg.hidden_size, cfg.hidden_size,
                                act="tanh", dtype=cfg.dtype)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        x = self.embeddings(input_ids, token_type_ids)
        mask = None
        if attention_mask is not None:
            # [B, S] 1/0 -> additive [B, 1, 1, S]
            mask = (1.0 - attention_mask[:, None, None, :].astype(x.dtype))
            mask = mask * -1e9
        x = self.encoder(x, mask)
        pooled = self.pooler(x[:, 0])
        return x, pooled


class BertForPretraining(nn.Layer):
    """MLM + NSP heads, returns the summed pretraining loss."""

    def __init__(self, cfg: BertConfig):
        super().__init__(dtype=cfg.dtype)
        self.bert = BertModel(cfg)
        self.transform = nn.Linear(cfg.hidden_size, cfg.hidden_size,
                                   act="gelu", dtype=cfg.dtype)
        self.transform_norm = nn.LayerNorm(cfg.hidden_size,
                                           epsilon=cfg.layer_norm_eps,
                                           dtype=cfg.dtype)
        self.mlm_bias = self.create_parameter([cfg.vocab_size], is_bias=True)
        self.nsp = nn.Linear(cfg.hidden_size, 2, dtype=cfg.dtype)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None,
                masked_positions=None):
        seq_out, pooled = self.bert(input_ids, token_type_ids,
                                    attention_mask)
        if masked_positions is not None:
            # gather hidden states at the masked positions [B, M] so the
            # vocab projection runs on M << S tokens
            seq_out = jnp.take_along_axis(
                seq_out, masked_positions[..., None], axis=1)
        h = self.transform_norm(self.transform(seq_out))
        # weight tying with the word embedding table (standard BERT)
        emb = F._val(self.bert.embeddings.word_embeddings.weight)
        logits = jnp.einsum("bsh,vh->bsv", h, emb) + self.mlm_bias
        nsp_logits = self.nsp(pooled)
        return logits, nsp_logits

    def loss(self, input_ids, mlm_labels, nsp_labels, token_type_ids=None,
             attention_mask=None, ignore_index=-100):
        logits, nsp_logits = self.forward(input_ids, token_type_ids,
                                          attention_mask)
        logp = F.log_softmax(logits.astype(jnp.float32), axis=-1)
        valid = (mlm_labels != ignore_index)
        safe = jnp.where(valid, mlm_labels, 0)
        tok_loss = -jnp.take_along_axis(logp, safe[..., None],
                                        axis=-1)[..., 0]
        denom = jnp.maximum(valid.sum(), 1)
        mlm_loss = jnp.where(valid, tok_loss, 0.0).sum() / denom
        nsp_logp = F.log_softmax(nsp_logits.astype(jnp.float32), axis=-1)
        nsp_loss = -jnp.take_along_axis(
            nsp_logp, nsp_labels[:, None], axis=-1).mean()
        return mlm_loss + nsp_loss
