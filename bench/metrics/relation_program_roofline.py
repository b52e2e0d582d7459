"""The least time the window's queries need on the device, the floor
bytes of ``roofline.py`` over the chip's HBM bandwidth, as a share of
the device's busy time in the traced window, in %."""


def read(rec):
    busy = rec.get("trace", {}).get("busy_s")
    floor = sum(s.get("floor_bytes", 0) for s in rec["served"])
    if not busy or not floor:
        return None
    return 100.0 * floor / rec["peaks"]["hbm_bytes_per_s"] / busy
