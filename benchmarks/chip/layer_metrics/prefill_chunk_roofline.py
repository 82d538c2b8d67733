"""Chunk-prefill step's share of its roofline, in percent: the least time
the chip needs for the traced chunks' work (valid tokens, live context)
over their device time."""
from _programs import CHUNK, roofline


def read(run):
    return roofline(run, CHUNK, run.chunk_work)
