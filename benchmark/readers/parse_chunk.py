"""Mean milliseconds of a `data.parse_chunk` span closed in the traced
part: one parse worker's whole work on one batch (protobuf scan, jpeg
decode into the batch, scalars)."""

import program_spans


def read(run):
    return program_spans.mean_ms(run, "data.parse_chunk")
