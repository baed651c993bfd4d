"""Statements completed in the window and later found correct, over the
window's real length (the statement in flight at the end finishes)."""


def compute(record):
    done = sum(1 for s in record["window"] if s.get("correct"))
    return done / record["window_s"] if done else None
