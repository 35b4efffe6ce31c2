"""Mean host milliseconds of the gateway's ingest packing
(``storm.gw.pack_ingest`` spans, ``_pack_ingest``) over the window."""

from storm_bench.program_spans import mean_ms


def read(run):
    return mean_ms(run, "pack_ingest")
