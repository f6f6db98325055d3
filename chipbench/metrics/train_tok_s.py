"""Tokens of the training steps completed in the window, over the
window: from the dispatch of its first step to the completion of its
last."""


def read(run):
    return run["window"]["tokens"] / run["window_s"]
