"""Model FLOP/s utilization of training: (6 N + 12 L d S) per token
times the window's tokens per second, over chips times the bf16 peak.
Recomputed work does not count."""


def read(run):
    c, ref = run["cfg"], run["ref"]
    flops = ref.train_flops_per_token(c, run["mix"]["seq_len"])
    rate = run["window"]["tokens"] / run["window_s"]
    return 100.0 * flops * rate / (run["chips"] * run["peaks"]["bf16_flop_s"])
