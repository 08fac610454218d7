"""Output tokens that reached the host inside the window, over the
window's seconds (host clock, ``emit_hook`` times)."""


def read(ctx):
    return ctx["tokens_in_window"] / ctx["window_s"]
