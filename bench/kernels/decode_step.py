"""Model operations and bytes of one decode step of one token at position
``pos`` through the whole dense GQA decoder: every projection and the tied
vocabulary projection (two operations per weight), plus attention over the
``pos + 1`` cached positions in every layer.  Bytes: every weight once in
bfloat16 and the live keys and values of every layer."""

BYTES_PER_ELEM = 2


def weights(c) -> int:
    d, ff, V = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    hd = c["head_dim"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    layer = d * q + 2 * d * kv + q * d + 3 * d * ff
    return c["num_hidden_layers"] * layer + V * d


def cost(c, pos: int):
    L, hd = c["num_hidden_layers"], c["head_dim"]
    hq, hk = c["num_attention_heads"], c["num_key_value_heads"]
    live = pos + 1
    flops = 2 * weights(c) + L * 4 * hq * hd * live
    nbytes = BYTES_PER_ELEM * (weights(c) + L * 2 * hk * hd * live)
    return float(flops), float(nbytes)
