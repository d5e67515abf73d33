"""Median host time of JaxEngine.decode_step in the window, logits pull
included."""

from bench.readers import median_ms, window_calls


def read(run):
    return median_ms(window_calls(run.rec, run.rec.decode))
