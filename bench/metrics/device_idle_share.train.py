"""Share of the traced training window in which no op runs on the device,
in %, averaged over the chips."""


def read(rec):
    tr = rec.get("trace")
    if rec["kind"] != "train" or not tr or not tr["devices"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
