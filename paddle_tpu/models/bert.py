"""BERT/ERNIE-style encoder (ERNIE-3.0/BERT-base pretrain with
Sharding-2).

Encoder built from the framework's TP layers + flash attention; MLM + NSP
heads for pretrain parity with the reference's ERNIE recipe.
"""
from __future__ import annotations

import numpy as np

from .. import nn
from ..core.tensor import Tensor
from ..distributed.fleet.meta_parallel.mp_layers import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
    _constraint,
)
from ..ops import common_nn as F
from ..ops import manipulation as M


class BertConfig:
    def __init__(
        self,
        vocab_size=30522,
        hidden_size=768,
        num_layers=12,
        num_heads=12,
        intermediate_size=3072,
        max_position_embeddings=512,
        type_vocab_size=2,
        dropout=0.1,
        remat=False,
    ):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.intermediate_size = intermediate_size
        self.max_position_embeddings = max_position_embeddings
        self.type_vocab_size = type_vocab_size
        self.dropout = dropout
        self.remat = remat


class BertSelfAttention(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.num_heads = cfg.num_heads
        self.head_dim = cfg.hidden_size // cfg.num_heads
        self.qkv = ColumnParallelLinear(cfg.hidden_size, 3 * cfg.hidden_size, gather_output=False)
        self.out = RowParallelLinear(cfg.hidden_size, cfg.hidden_size, input_is_parallel=True)
        self.dropout = cfg.dropout

    def forward(self, x, attn_mask=None):
        b, s, _ = x.shape
        qkv = M.reshape(self.qkv(x), [b, s, 3, self.num_heads, self.head_dim])
        q = M.squeeze(M.slice(qkv, [2], [0], [1]), 2)
        k = M.squeeze(M.slice(qkv, [2], [1], [2]), 2)
        v = M.squeeze(M.slice(qkv, [2], [2], [3]), 2)
        out = F.scaled_dot_product_attention(
            q, k, v, attn_mask=attn_mask, dropout_p=self.dropout,
            training=self.training,
        )
        return self.out(M.reshape(out, [b, s, self.num_heads * self.head_dim]))


class BertLayer(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.attn = BertSelfAttention(cfg)
        self.ln1 = nn.LayerNorm(cfg.hidden_size)
        self.fc1 = ColumnParallelLinear(cfg.hidden_size, cfg.intermediate_size, gather_output=False)
        self.fc2 = RowParallelLinear(cfg.intermediate_size, cfg.hidden_size, input_is_parallel=True)
        self.ln2 = nn.LayerNorm(cfg.hidden_size)
        self.act = nn.GELU()
        self.dropout = nn.Dropout(cfg.dropout)
        self._cfg = cfg

    def _inner(self, x, attn_mask=None):
        x = self.ln1(x + self.dropout(self.attn(x, attn_mask)))
        x = _constraint(x, "dp", "sp", None)
        x = self.ln2(x + self.dropout(self.fc2(self.act(self.fc1(x)))))
        return _constraint(x, "dp", "sp", None)

    def forward(self, x, attn_mask=None):
        if self._cfg.remat:
            from ..distributed.fleet.utils import recompute

            return recompute(self._inner, x)
        return self._inner(x, attn_mask)


class Bert(nn.Layer):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.cfg = cfg
        self.word_emb = VocabParallelEmbedding(cfg.vocab_size, cfg.hidden_size)
        self.pos_emb = nn.Embedding(cfg.max_position_embeddings, cfg.hidden_size)
        self.type_emb = nn.Embedding(cfg.type_vocab_size, cfg.hidden_size)
        self.ln = nn.LayerNorm(cfg.hidden_size)
        self.dropout = nn.Dropout(cfg.dropout)
        self.layers = nn.LayerList([BertLayer(cfg) for _ in range(cfg.num_layers)])
        self.pooler = nn.Linear(cfg.hidden_size, cfg.hidden_size)
        self.pooler_act = nn.Tanh()
        # MLM head
        self.mlm_transform = nn.Linear(cfg.hidden_size, cfg.hidden_size)
        self.mlm_ln = nn.LayerNorm(cfg.hidden_size)
        # NSP head
        self.nsp = nn.Linear(cfg.hidden_size, 2)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        b, s = input_ids.shape
        pos = M.reshape(Tensor(np.arange(s, dtype=np.int64)), [1, s])
        x = self.word_emb(input_ids) + self.pos_emb(pos)
        if token_type_ids is not None:
            x = x + self.type_emb(token_type_ids)
        x = self.dropout(self.ln(x))
        x = _constraint(x, "dp", "sp", None)
        for layer in self.layers:
            x = layer(x, attention_mask)
        pooled = self.pooler_act(self.pooler(x[:, 0]))
        mlm = self.mlm_ln(nn.functional.gelu(self.mlm_transform(x)))
        logits = F.linear(mlm, M.t(self.word_emb.weight))
        nsp_logits = self.nsp(pooled)
        return logits, nsp_logits


def bert_base(**kw):
    return Bert(BertConfig(**kw))


def ernie_base(**kw):
    """ERNIE-3.0-base shape."""
    kw.setdefault("vocab_size", 40000)
    return Bert(BertConfig(**kw))


def bert_pretrain_loss_fn(outputs, labels_array):
    """MLM loss for compiled step (labels: next-token-style mlm labels,
    -100 = unmasked)."""
    import jax
    import jax.numpy as jnp

    logits = outputs[0] if isinstance(outputs, (tuple, list)) else outputs
    labels = labels_array.astype(jnp.int32)
    valid = labels != -100
    safe = jnp.where(valid, labels, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    picked = jnp.take_along_axis(logp, safe[..., None], -1)[..., 0]
    return -jnp.sum(jnp.where(valid, picked, 0.0)) / jnp.maximum(valid.sum(), 1)
