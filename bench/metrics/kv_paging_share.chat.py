"""Share of the decode step's device time in the paged-KV gather and
scatter (scopes ``kv_dense_view``, ``kv_writeback``), in the chat cells
(%)."""
from bench import program_trace


def read(run):
    return program_trace.kv_paging_share(run)
