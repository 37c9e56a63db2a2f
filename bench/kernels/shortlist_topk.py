"""Operations and bytes of the IVF shortlist search for the rows it scores
(``kernels/cache_topk`` ``shortlist_topk``): each shortlisted row is read
once in float32 with its type code and its shortlist id, and scored with
one multiply-add per dimension.  The kernel reads the whole table once per
query tile and shortlist tile; that is not work the search needs, so it
lowers the share of the roofline rather than raise the count.

In the device trace the kernel is a custom call with two outputs, float32
scores and int32 row ids, each (padded queries, k)."""
import re


def is_call(op_name: str, c) -> bool:
    return bool(re.match(r"%\S+ = \(f32\[\d+,\d+\]\{[^}]*\}, "
                         r"s32\[\d+,\d+\]\{[^}]*\}\) custom-call\(",
                         op_name))


def cost(dim: int, rows: int):
    flops = 2 * dim * rows
    nbytes = rows * (4 * dim + 4 + 4) + 4 * dim
    return float(flops), float(nbytes)
