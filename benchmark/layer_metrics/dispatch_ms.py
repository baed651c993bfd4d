"""plan cache + compile: median self time of ``ob:plan.dispatch`` (plan
fingerprint, executable lookup, input signature, the call that enqueues the
program; a compile inside it is its child ``ob:xla.compile``) over the traced
statements."""

from benchmark.harness import program_spans


def compute(record):
    return program_spans.self_ms(record, "plan.dispatch")
