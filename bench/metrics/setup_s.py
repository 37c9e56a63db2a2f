"""Process start to the first due request, in s: weights from the seed,
compiles or compile-cache loads, warm-up of the cell's shapes and, where the
mix asks for one, the semantic-cache fill."""


def read(ctx):
    return float(ctx.setup_s)
