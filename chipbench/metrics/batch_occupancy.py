"""Mean lanes that emitted a token per decode step in the window: output
tokens over the engine's decode steps (``ServeEngine.steps``)."""


def read(run):
    w = run["window"]
    return w["decode_tokens"] / w["decode_steps"] if w["decode_steps"] else None
