"""Model operations and bytes of one decode step of one token at position
``pos`` through the whole model: ``weights(c)``, the weight elements a step
reads, and ``cost(c, pos)``, its (operations, bytes).  Both are the
configuration's family's (``bench/families/<family>.py``, ``decode_weights``
and ``decode_cost``); for the dense family, every projection and the tied
vocabulary projection (two operations per weight), plus attention over the
``pos + 1`` cached positions in every layer, and in bytes every weight once
in bfloat16 and the live keys and values of every layer."""
import os

from bench import common

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def weights(c) -> int:
    return common.load_family(c, BENCH_DIR).decode_weights(c)


def cost(c, pos: int):
    return common.load_family(c, BENCH_DIR).decode_cost(c, pos)
