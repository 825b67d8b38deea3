"""First-class model families beyond the vision zoo."""
from .transformer import (TransformerLM, MultiHeadAttention,
                          TransformerEncoderLayer, transformer_lm_tiny,
                          transformer_lm_small, transformer_lm_base, tp_rules)
from .moe_transformer import MoETransformerLM, moe_lm_tiny
from .lstm_lm import RNNModel
from .eva_lm import EvaDecoder, EvaAttention, EvaDecoderLayer, eva_lm_tiny
from .hybrid_ssm import (HybridDecoder, HybridDecoderLayer, MambaMixer,
                         GroupedAttention, hybrid_ssm_tiny)
