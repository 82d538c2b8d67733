"""Whole chunk-prefill step against the chip's peak, in percent: model
FLOPs of the traced chunks' valid tokens over their device time times the
peak FLOP/s."""
from _programs import CHUNK, chunk_work, mfu


def read(run):
    return mfu(run, CHUNK, chunk_work(run))
