"""plan cache + compile: sum of ``lower_s + xla_compile_s`` over the
warm-up's statements (what the persistent cache saves shows here).  Left
out where the path records neither (PX today: every first run lowers, so
a sum of 0 means "not recorded", not "nothing compiled")."""


def compute(record):
    xs = [s["audit"]["lower_s"] + s["audit"]["xla_compile_s"]
          for s in record["warmup"] if s.get("audit")]
    return sum(xs) if xs and sum(xs) > 0 else None
