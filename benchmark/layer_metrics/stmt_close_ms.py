"""session layer: median self time of ``ob:plan.record`` + ``ob:statement.close``
over the traced statements: what the ledgers (plan monitor, plan choice,
feedback, plan history, metrics, trace retention, audit row, time model) cost
a statement (ROADMAP D7)."""

from benchmark.harness import program_spans


def compute(record):
    return program_spans.self_ms(record, "plan.record", "statement.close")
