"""Parameter and FLOP counts of a dense decoder, from its published sizes.

Everything takes the configuration file's dict (HuggingFace key names).
What is counted, and what is not:

- ``train_flops_per_token``: the matrix multiplications of the forward and
  backward passes (2 FLOP a multiply-add, backward twice the forward) over
  the layers' projections and the output head, plus causal attention
  (scores and weighted values, only the lower triangle). Not counted: the
  embedding lookup (a gather, not a matmul), norms, rotary, softmax,
  the optimizer, and whatever rematerialisation computes a second time.
  (``LlamaConfig.flops_per_token`` counts the lookup as 6N matmul work and
  the attention square in full; this function does neither, so a
  utilization worked from it reads lower.)
- ``flash_train_flops``: what the forward, dq and dk/dv kernels of one
  training step *need*: two matmuls forward, five backward (scores again,
  dV, dP, dQ, dK), causal half. The split dq and dk/dv kernels compute
  scores and dP twice, and remat runs the forward kernel twice; neither
  is required work, so neither is counted.
"""

from __future__ import annotations


def layer_params(m: dict) -> int:
    h, mlp = m["hidden_size"], m["intermediate_size"]
    nh, nkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    return h * (nh + 2 * nkv) * hd + nh * hd * h + 3 * h * mlp + 2 * h


def embed_and_head_params(m: dict) -> int:
    tied = 1 if m.get("tie_word_embeddings") else 2
    return tied * m["vocab_size"] * m["hidden_size"]


def num_params(m: dict) -> int:
    return (m["num_hidden_layers"] * layer_params(m)
            + embed_and_head_params(m) + m["hidden_size"])


def matmul_params(m: dict) -> int:
    """Weights that a token is multiplied by: projections and the head."""
    per_layer = layer_params(m) - 2 * m["hidden_size"]
    return (m["num_hidden_layers"] * per_layer
            + m["vocab_size"] * m["hidden_size"])


def attention_flops_per_token(m: dict, seq: int, *, backward: bool) -> float:
    """Causal attention, averaged over the positions of a sequence."""
    per_matmul = 2.0 * m["num_attention_heads"] * m["head_dim"] * seq / 2.0
    n_matmuls = 2 + (5 if backward else 0)
    return m["num_hidden_layers"] * n_matmuls * per_matmul


def train_flops_per_token(m: dict, seq: int) -> float:
    return 6.0 * matmul_params(m) + attention_flops_per_token(
        m, seq, backward=True)


def flash_train_flops(m: dict, batch: int, seq: int) -> float:
    """Required FLOPs of the three flash kernels over one training step."""
    return batch * seq * attention_flops_per_token(m, seq, backward=True)


def flash_train_bytes(m: dict, batch: int, seq: int) -> float:
    """Least HBM traffic of the three kernels: q, o, do, dq once each at
    the query heads, k, v, dk, dv at the key/value heads, bf16."""
    nh, nkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    elems = batch * seq * hd * (4 * nh + 4 * nkv)
    return m["num_hidden_layers"] * 2.0 * elems
