"""BERT encoder (BASELINE config 3; gluon-nlp BERT lineage).

Gluon blocks over the attention op — covers the reference's contrib
BERT-era ops (src/operator/contrib/transformer.cc: interleaved matmul
self-attention) with dot_product_attention: on a TPU the blockwise
Pallas kernel pair, with a key-padding mask the XLA composition.
"""
from __future__ import annotations

from .. import initializer as init_mod
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..gluon.parameter import Parameter
from ..ops.registry import invoke


class BERTSelfAttention(HybridBlock):
    def __init__(self, units, num_heads, dropout=0.1, **kwargs):
        super().__init__(**kwargs)
        assert units % num_heads == 0
        self._units = units
        self._heads = num_heads
        self.qkv = nn.Dense(3 * units, flatten=False, in_units=units)
        self.proj = nn.Dense(units, flatten=False, in_units=units)
        self.dropout = nn.Dropout(dropout)

    def forward(self, x, mask=None):
        B, T, D = x.shape
        H = self._heads
        qkv = self.qkv(x)
        qkv = qkv.reshape((B, T, 3, H, D // H)).transpose((2, 0, 3, 1, 4))
        q, k, v = qkv[0], qkv[1], qkv[2]
        att_mask = None
        if mask is not None:
            att_mask = mask.reshape((B, 1, 1, T))
        out = invoke("dot_product_attention", q, k, v, *(
            [att_mask] if att_mask is not None else []))
        out = out.transpose((0, 2, 1, 3)).reshape((B, T, D))
        return self.dropout(self.proj(out))


class BERTEncoderLayer(HybridBlock):
    def __init__(self, units, hidden_size, num_heads, dropout=0.1, **kwargs):
        super().__init__(**kwargs)
        self.attention = BERTSelfAttention(units, num_heads, dropout)
        self.ln1 = nn.LayerNorm(in_channels=units)
        self.ffn1 = nn.Dense(hidden_size, flatten=False, in_units=units)
        self.ffn2 = nn.Dense(units, flatten=False, in_units=hidden_size)
        self.ln2 = nn.LayerNorm(in_channels=units)
        self.dropout = nn.Dropout(dropout)

    def forward(self, x, mask=None):
        x = self.ln1(x + self.attention(x, mask))
        h = self.ffn2(invoke("gelu", self.ffn1(x)))
        return self.ln2(x + self.dropout(h))


class BERTEncoder(HybridBlock):
    def __init__(self, num_layers=12, units=768, hidden_size=3072,
                 num_heads=12, dropout=0.1, **kwargs):
        super().__init__(**kwargs)
        for i in range(num_layers):
            self.register_child(
                BERTEncoderLayer(units, hidden_size, num_heads, dropout),
                f"layer{i}")

    def forward(self, x, mask=None):
        for layer in self._children.values():
            x = layer(x, mask)
        return x


class BERTModel(HybridBlock):
    """Token+segment+position embeddings → encoder → MLM + NSP heads."""

    def __init__(self, vocab_size=30522, num_layers=12, units=768,
                 hidden_size=3072, num_heads=12, max_length=512,
                 type_vocab_size=2, dropout=0.1, **kwargs):
        super().__init__(**kwargs)
        self.word_embed = nn.Embedding(vocab_size, units)
        self.token_type_embed = nn.Embedding(type_vocab_size, units)
        self.pos_embed = Parameter("pos_embed", shape=(max_length, units),
                                   init=init_mod.Normal(0.02))
        self.embed_ln = nn.LayerNorm(in_channels=units)
        self.embed_dropout = nn.Dropout(dropout)
        self.encoder = BERTEncoder(num_layers, units, hidden_size, num_heads,
                                   dropout)
        self.pooler = nn.Dense(units, activation="tanh", in_units=units)
        self.mlm_decoder = nn.Dense(vocab_size, flatten=False, in_units=units)
        self.nsp_classifier = nn.Dense(2, in_units=units)

    def forward(self, tokens, token_types=None, valid_length=None):
        B, T = tokens.shape
        x = self.word_embed(tokens)
        if token_types is not None:
            x = x + self.token_type_embed(token_types)
        x = x + self.pos_embed.data()[:T].expand_dims(0)
        x = self.embed_dropout(self.embed_ln(x))
        mask = None
        if valid_length is not None:
            from .. import ndarray as nd
            steps = nd.arange(0, T, ctx=tokens.ctx)
            mask = (steps.expand_dims(0) < valid_length.expand_dims(1))
        x = self.encoder(x, mask)
        pooled = self.pooler(x[:, 0])
        return self.mlm_decoder(x), self.nsp_classifier(pooled)
