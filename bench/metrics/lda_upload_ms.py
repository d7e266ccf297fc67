"""lda_upload_ms (ms, program span): the median over the window's sweeps of
the time ``gibbs_step`` spends sending the corpus (``docs`` and ``mask``) to
the device, its ``lda.upload`` spans.  Moves lda_tokens_per_s."""

from bench import program_spans


def read(rec):
    per_sweep = program_spans.per_parent_ms(program_spans.select(rec) or [],
                                            ("lda.upload",))
    return program_spans.median(per_sweep.values())
