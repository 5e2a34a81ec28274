"""Share of the encoded blocks the error bound demoted -- some valid
dictionary entry passed the min/max gate and the bound excluded every such
entry -- counted on the device by the direct scan: growth of
``repro_encode_bound_demotions_total{path="direct"}`` over growth of
``repro_encode_blocks_total``, in percent.  A program without the counter
gives nothing."""
NAME = "repro_encode_bound_demotions_total"
LABELS = {"path": "direct"}


def read(ctx):
    if not any(v["labels"] == LABELS
               for v in ctx.counters_after.get(NAME, {}).get("values", [])):
        return None
    blocks = ctx.counter("repro_encode_blocks_total")
    if blocks <= 0:
        return None
    return 100.0 * ctx.counter(NAME, LABELS) / blocks
