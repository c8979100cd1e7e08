"""GB/s the streamed Cholesky kernel's DMAs move: the ``stream_bytes`` tag
of the window's ``factorize.window_batched`` spans (the bytes for one
matrix, from the kernel's block plan) times the matrices each factorized
(its ``b`` tag), over the device time of the executables that launched the
streamed kernel.  None where the program tags no such span (a program
without the streamed sweep) or the trace holds no such run."""

KERNEL = r"band_cholesky_stream"


def read(ctx):
    spans = [s for s in ctx["spans"]
             if s["name"] == "factorize.window_batched"
             and "stream_bytes" in s["tags"]]
    runs = ctx["trace"].runs_with_op(KERNEL)
    seconds = sum(d for _, d in runs)
    if not spans or not runs or seconds <= 0:
        return None
    moved = sum(s["tags"]["stream_bytes"] * s["tags"]["b"] for s in spans)
    return moved / seconds / 1e9
