"""lda_tokens_per_s (tokens/s, host clock): corpus tokens resampled over the
wall time of whole sweeps, each ended by ``block_until_ready``, from the
window's start to the end of its last sweep."""


def read(rec):
    return rec.get("tokens_per_s")
