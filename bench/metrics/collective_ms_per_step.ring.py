"""Device milliseconds of collective ops per training step, averaged over
the chips. Nothing to read on one chip."""


def read(rec):
    tr = rec.get("trace")
    if not tr or not tr["devices"] or rec["chips"] < 2 \
            or rec["kind"] != "train":
        return None
    steps = tr["steps"].get("bench.train_step", 0)
    coll = tr["op_s"].get("collective", 0.0)
    if not steps or coll <= 0.0:
        return None
    return 1e3 * coll / steps
