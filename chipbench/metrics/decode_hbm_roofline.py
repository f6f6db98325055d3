"""Share of the chip's HBM bandwidth that the serving program's calls in
the traced stretch needed, over the device's busy time there.

Needed bytes, counted from shapes and live positions: the weights at
bf16 once per call; for each position fed, the keys and values of every
position it attends to, and one key/value write; the float32 logits of
each emitted token.  Not counted: the program's float32 weights, its
view padded to ``max_seq``, logits nobody reads."""


def read(run):
    t, s = run.get("trace"), run.get("traced")
    if not t or not s or not s["calls"] or t["busy_s"] <= 0:
        return None
    c, ref = run["cfg"], run["ref"]
    kv = ref.kv_bytes_per_position(c)
    need = (s["calls"] * 2 * ref.param_count(c)
            + (s["context_sum"] + s["positions"]) * kv
            + s["decode_tokens"] * c["vocab_size"] * 4)
    return 100.0 * need / (t["busy_s"] * run["peaks"]["hbm_bytes_s"])
