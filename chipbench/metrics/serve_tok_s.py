"""Output tokens emitted inside the window, over the window's seconds."""


def read(run):
    return run["window"]["decode_tokens"] / run["window_s"]
