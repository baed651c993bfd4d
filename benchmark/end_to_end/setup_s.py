"""Process start to the first statement of the window: device, boot, load,
ANALYZE, the tables' device copies, warm-up."""


def compute(record):
    return record["setup_seconds"]
