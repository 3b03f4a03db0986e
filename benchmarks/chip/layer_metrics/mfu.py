"""Model FLOP/s utilisation of the whole step, %: the logical model
operations of the steps done in the traced window (the ``train_flops`` of
the cell's architecture module, ``archs/<name>.py``, by the rules of
``work.py``: projections, attention over the positions really attended,
the head where its logits are used; no recomputation, no padding), over
the window, the chips and the chip's bf16 peak (the separable TimeFloats
path multiplies in bf16)."""


def read(ctx):
    flops = ctx.get("flops")
    if not flops:
        return None
    peak = ctx["peaks"]["bf16_flops"]
    return 100.0 * flops / (ctx["span_s"] * ctx["chips"] * peak)
