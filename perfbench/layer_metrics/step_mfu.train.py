"""The whole train step's share of the chips' peak: closed-form forward +
backward operations of the REAL tokens the traced window trained on
(`perfbench/flops/<reference>.py`), over the window's seconds, over chips x
the published bf16 peak (`perfbench/peaks.json`)."""


def read(view):
    return view.mfu_pct()
