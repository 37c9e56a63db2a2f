"""Operations and bytes of one paged decode attention call: one layer, one
slot, one query token at position ``pos`` attending to the ``pos + 1``
cached keys and values (``kernels/decode_attention``, S == 1).

Counted as the algorithm needs them, from the shapes: the query and output
rows, and each live key and value once in bfloat16; two multiply-adds (QK,
PV) per query head, live key and head dimension.  Pages the kernel reads
beyond the live tokens are not work the algorithm needs, so they lower the
kernel's share of its roofline rather than raise the count.

In the device trace the kernel is a custom call whose bfloat16 output is
(slots x kv heads, query heads per kv head, head size); the prefill kernel's
second dimension is that group times the tokens per row, so it never
matches.
"""
import re

BYTES_PER_ELEM = 2          # bfloat16 cache and activations


def is_call(op_name: str, c) -> bool:
    hk, hd = c["num_key_value_heads"], c["head_dim"]
    g = c["num_attention_heads"] // hk
    m = re.match(rf"%\S+ = bf16\[(\d+),{g},{hd}\]\{{[^}}]*\}} custom-call\(",
                 op_name)
    return bool(m) and int(m.group(1)) % hk == 0


def cost(c, pos: int):
    hq, hk, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    live = pos + 1
    flops = 4 * hq * hd * live
    nbytes = BYTES_PER_ELEM * (2 * hk * hd * live + 2 * hq * hd)
    return float(flops), float(nbytes)
