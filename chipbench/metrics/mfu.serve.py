"""Model FLOPs of the positions the serving program processed in the
window (prompt and output), over the window and the chip's bf16 peak:
per position 2 per weight it passes plus attention over its context,
and the output projection only for emitted tokens."""


def read(run):
    w, c, ref = run["window"], run["cfg"], run["ref"]
    if not w["positions"]:
        return None
    per_pos = ref.forward_flops(c, 0, unembed=False)
    attn = ref.forward_flops(c, 1, unembed=False) - per_pos
    flops = (w["positions"] * per_pos + w["context_sum"] * attn
             + w["decode_tokens"] * 2 * c["hidden_size"] * c["vocab_size"])
    return 100.0 * flops / (run["window_s"] * run["chips"]
                            * run["peaks"]["bf16_flop_s"])
