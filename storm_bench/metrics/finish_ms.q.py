"""Mean host milliseconds of the gateway's result scatter
(``storm.gw.scatter`` spans: answers into the requests' buffers and the
result list of ``tick_finish``) over the window."""

from storm_bench.program_spans import mean_ms


def read(run):
    return mean_ms(run, "scatter")
