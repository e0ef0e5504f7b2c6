"""Output tokens the host received inside the window — from every
request, warm-up ones and those still running at its close too — over
the window's seconds."""


def read(ctx):
    return ctx.window.tokens_received / ctx.window.seconds
