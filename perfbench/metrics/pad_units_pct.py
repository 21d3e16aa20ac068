"""Pixel stage: the share of its data units that are capacity padding,
100 x (units_cap - units) / units_cap, the mean per batch of the window
(`drivers/ingest_crop.py` reads both from each batch's decoder: the unit
capacity and the batch's units)."""


def read(ctx):
    return ctx["counters"].get("pad_units_pct")
