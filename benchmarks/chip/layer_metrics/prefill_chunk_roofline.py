"""Chunk-prefill step's share of its roofline, in percent: the least time
the chip needs for the traced chunks' work (valid tokens, live context)
over their device time."""
from _programs import CHUNK, chunk_work, roofline


def read(run):
    return roofline(run, CHUNK, chunk_work(run))
