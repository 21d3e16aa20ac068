"""Device idle share of the traced window in the ingest cells, in percent:
1 - (union of the device's operation intervals) / (window), the mean over
the chips used."""


def read(ctx):
    return ctx["trace"].idle_pct()
