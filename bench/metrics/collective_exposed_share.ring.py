"""Share of the step's device time in which a collective op (a sync one,
or the wait of an async one) runs and no compute does, in %, averaged
over the chips. Nothing to read on one chip."""


def read(rec):
    tr = rec.get("trace")
    if not tr or not tr["devices"] or rec["chips"] < 2 \
            or tr["op_s"].get("collective", 0.0) <= 0.0:
        return None
    return 100.0 * tr["exposed_collective_s"] / tr["busy_s"]
