"""Operations a decoder step needs, counted from shapes (the yardstick the
utilization readers divide by).  A multiply-add counts as two operations.
Only what the algorithm needs is counted: causal attention over the
positions a query may see, and the output head at the positions whose
logits the step returns.  Keys are the configuration's published names.
"""
from __future__ import annotations


def _per_token_linear(c: dict) -> int:
    d, f = c["hidden_size"], c["intermediate_size"]
    q = c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    return 2 * (d * q + 2 * d * kv + q * d + 3 * d * f)


def prefill_flops(c: dict, batch: int, length: int) -> int:
    """A prefill of ``batch`` prompts of ``length`` tokens that returns the
    logits of the last position."""
    L = c["num_hidden_layers"]
    qk_pv = 4 * c["num_attention_heads"] * c["head_dim"] * length * (length + 1) // 2
    per_seq = L * (length * _per_token_linear(c) + qk_pv) + 2 * c["hidden_size"] * c["vocab_size"]
    return batch * per_seq


def decode_flops(c: dict, batch: int, context: int) -> int:
    """One decode step whose new token attends to ``context`` positions
    (itself included), returning its logits."""
    L = c["num_hidden_layers"]
    attn = 4 * c["num_attention_heads"] * c["head_dim"] * context
    return batch * (L * (_per_token_linear(c) + attn) + 2 * c["hidden_size"] * c["vocab_size"])


def request_flops(c: dict, batch: int, length: int, new_tokens: int) -> int:
    """A served request: its prefill (first token) and the ``new_tokens - 1``
    decode steps that produce the rest."""
    return prefill_flops(c, batch, length) + sum(
        decode_flops(c, batch, length + t) for t in range(1, new_tokens))
