"""Decode step's share of its roofline, in percent: the least time the chip
needs for the traced steps' work (real lanes, live context) over their
device time, gather and scatter included."""
from _programs import DECODE, decode_work, roofline


def read(run):
    return roofline(run, DECODE, decode_work(run))
