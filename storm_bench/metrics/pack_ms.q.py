"""Mean host milliseconds of the gateway's query packing
(``storm.gw.pack_queries`` spans, ``_pack_queries``) over the window."""

from storm_bench.program_spans import mean_ms


def read(run):
    return mean_ms(run, "pack_queries")
