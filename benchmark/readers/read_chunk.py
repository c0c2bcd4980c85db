"""Mean milliseconds of a `data.read_chunk` span closed in the traced part:
record read, shuffle and ROI resolve of one batch, serial in the dataset's
prefetch thread."""

import program_spans


def read(run):
    return program_spans.mean_ms(run, "data.read_chunk")
