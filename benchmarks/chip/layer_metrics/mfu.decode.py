"""Whole decode step against the chip's peak, in percent: model FLOPs of
the traced steps' live lanes over their device time times the peak FLOP/s."""
from _programs import DECODE, decode_work, mfu


def read(run):
    return mfu(run, DECODE, decode_work(run))
