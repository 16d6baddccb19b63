"""Share of the traced window in which no operation ran on the chip:
1 - (union of the device-operation intervals / window), in percent."""
LAYER = "device"
UNIT = "%"
MOVES = "time_to_eps_s"


def read(record):
    tr = record.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
