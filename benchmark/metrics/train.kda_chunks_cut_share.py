"""Mean of the attribute ``kda_chunks_cut_share`` (the chunks of the delta rule in which a document starts over all chunks, every KDA layer: above 0 the packing reached the recurrence) over the ``train.step_device`` spans of every step of the window, in percent."""

from benchmark import step_records


def read(obs):
    share = step_records.read(obs, lambda steps: step_records.attr_mean(steps, "kda_chunks_cut_share"))
    return None if share is None else 100.0 * share
