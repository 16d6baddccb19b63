"""The whole round's share of the chip's peak: the operations a round
requires (``bench/flops.py``) times the rounds per second of the window,
over the chip's bf16 peak, in percent.  The program's float32 products
run as one bf16 pass at the default precision, so bf16 is the peak."""
LAYER = "whole round"
UNIT = "%"
MOVES = "time_to_eps_s"


def read(record):
    win, flops = record.get("window"), record.get("flops_per_round")
    if not win or not flops or win["seconds"] <= 0:
        return None
    rate = win["rounds"] / win["seconds"]
    return 100.0 * flops * rate / (record["chips"] * record["peak"]["bf16_flops_per_s"])
