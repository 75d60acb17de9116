"""Work the algorithm needs, counted from a configuration file.

Counts follow the served model, not the program's implementation: a
change that pads, recomputes or moves more bytes does not raise them.
Keys are the configuration file's (Hugging Face names).
"""

from __future__ import annotations


def _dims(conf: dict):
    d = conf["hidden_size"]
    h = conf["num_attention_heads"]
    kv = conf["num_key_value_heads"]
    hd = conf.get("head_dim", d // h)
    return d, h, kv, hd, conf["intermediate_size"], conf["vocab_size"]


def layer_params(conf: dict) -> int:
    """Matrix parameters of one decoder layer (attention + gated MLP)."""
    d, h, kv, hd, f, _ = _dims(conf)
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def head_flops(conf: dict) -> int:
    """One position through the LM head."""
    d, *_, v = _dims(conf)
    return 2 * d * v


def attention_flops(conf: dict, context: int) -> int:
    """QK and PV products of one query over ``context`` keys, all layers."""
    _, h, _, hd, _, _ = _dims(conf)
    return 4 * h * hd * context * conf["num_hidden_layers"]


def token_flops(conf: dict, context: int) -> int:
    """One position through every layer, attending to ``context`` keys."""
    return (2 * layer_params(conf) * conf["num_hidden_layers"]
            + attention_flops(conf, context))


def request_flops(conf: dict, prompt_len: int, served: int) -> int:
    """Useful FLOPs of a request with ``served`` tokens out.

    The unpadded prompt runs every layer under a causal mask, and its
    last position goes through the head; each served token after the
    first is fed back at its context length and gives one more head
    position.  Padding is not counted, so padding waste lowers ``mfu``.
    """
    if served <= 0:
        return 0
    # positions 0 .. prompt_len + served - 2 enter the layers; position
    # i attends to i + 1 keys
    n_pos = prompt_len + served - 1
    dense = 2 * layer_params(conf) * conf["num_hidden_layers"] * n_pos
    attn = attention_flops(conf, 1) * n_pos * (n_pos + 1) // 2
    return dense + attn + served * head_flops(conf)


def encode_bytes(elems: int, index_bits: int, in_bytes: int = 2) -> float:
    """HBM bytes the boundary encode needs: the model-dtype tensor read
    once (bf16, 2 bytes an element) and the packed indices written."""
    return elems * (in_bytes + index_bits / 8.0)
