"""Backend-compile seconds inside the whole window (JAX monitoring events,
persistent-cache loads included): every prefill call traces its layer scan
anew and loads or compiles it."""


def read(ctx, res):
    return ctx.compile_in_window[0]
