"""plan cache + compile: ``jax.compile_ns{stage=trace}`` +
``jax.compile_ns{stage=lower}`` at the window's start: the self time of JAX's
own tracing and lowering events over set-up and warm-up, host work that no
compile cache keeps."""


def compute(record):
    c = record["counters_before"]
    keys = ("jax.compile_ns{stage=trace}", "jax.compile_ns{stage=lower}")
    if not any(k in c for k in keys):
        return None
    return sum(c.get(k, 0.0) for k in keys) * 1e-9
