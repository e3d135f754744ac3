"""Engine step: median, over the window's whole batches, of a batch's tokens
over its wall time from submit to last token. Steadier than ``out_tok_s``
against one stalled batch, and blind to the time between batches, so it
stands beside the end-to-end rate and not in its place."""
import statistics


def read(obs):
    rates = obs.get("batch_tok_s")
    return statistics.median(rates) if rates else None
