"""session layer: median self time of ``ob:parse`` (``parse_sql``: lexer and
parser, before the plan cache is probed) over the traced statements."""

from benchmark.harness import program_spans


def compute(record):
    return program_spans.self_ms(record, "parse")
