"""Model FLOP/s of the serving window over the chip's bf16 peak, in %: the
forward operations of every finished request (its prompt and output
tokens through the layers, attention over each token's context, the LM
head once per output token), over the seconds from the first arrival to
the last token."""


def read(rec):
    if rec["kind"] != "serve" or not rec["flops"]:
        return None
    achieved = rec["flops"] / rec["window_s"]
    return 100.0 * achieved / (rec["chips"] * rec["peaks"]["bf16_flops"])
