"""Model families.

``transformer`` — the flagship TPU-native decoder LM with full 5-axis
(dp/pp/tp/sp/ep) sharding support; drives ``__graft_entry__.dryrun_multichip``.
``lstm_lm`` — LSTM language model (BASELINE config 5, reference example/rnn).
``bert`` — BERT-style encoder (BASELINE config 3, gluon-nlp lineage).
``mla_moe`` — causal decoder with latent attention (MLA), routed
feed-forward layers of which this chip holds a share, and a multi-token-
prediction module (the DeepSeek-V3 family's layer equations).
``falcon_h1`` — causal decoder whose every block runs a Mamba-2 state-space
mixer beside grouped-query attention, then a gated MLP, with fixed
multipliers (the Falcon-H1 family's layer equations).
``nemotron_h`` — causal decoder whose layers differ in kind, each one
pre-normed residual branch chosen by a pattern: a Mamba-2 mixer, a latent
mixture of experts (ungated ``relu²`` experts in a narrower latent) or
attention without a position embedding, with a multi-token-prediction
module (the Nemotron-H family's layer equations).
Vision models live in ``gluon.model_zoo.vision`` (reference layout).
"""
from . import transformer
from .transformer import TransformerLM, TransformerConfig
from .lstm_lm import LSTMLanguageModel
from .bert import BERTEncoder, BERTModel
from .mla_moe import MLAMoEDecoder
from .falcon_h1 import FalconH1Decoder
from .nemotron_h import NemotronHDecoder
