"""Model FLOPs per token (6N plus causal attention, recomputation not
counted) times the window's training tokens per second, over the int8 peak:
the highest rate any GEMM of the step can run at."""

from bench import flops


def read(run):
    out = run["out"]
    rate = out.get("train_tokens_per_s")
    if not rate:
        return None
    per_token = flops.train_per_token(run["model"], run["traffic"]["seq"])
    return 100.0 * per_token * rate / run["peaks"]["int8_ops"]
