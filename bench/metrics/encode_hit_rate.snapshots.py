"""Share of the encoded blocks that hit the dictionary: growth of
``repro_encode_hits_total`` over growth of ``repro_encode_blocks_total``,
in percent."""


def read(ctx):
    blocks = ctx.counter("repro_encode_blocks_total")
    if blocks <= 0:
        return None
    return 100.0 * ctx.counter("repro_encode_hits_total") / blocks
