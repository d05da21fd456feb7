"""The rate at which the stages took their inputs out of HBM-resident
fields, in GB/s (1e9 B), against the v5e's 819: the bytes of every batch
taken from an input that already lives on the device (the program's
``resident_in_bytes`` counter, all streams) over the device's busy time
summed over the cell's chips.  Nothing where the program keeps no such
counter or the device was never busy."""


def read(r):
    moved = sum(r.spans.totals("resident_in_bytes").values())
    busy = sum(r.trace["busy_s_by_chip"].values())
    if not moved or not busy:
        return None
    return moved / busy / 1e9
