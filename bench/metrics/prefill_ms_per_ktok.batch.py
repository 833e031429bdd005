"""Device time of the paged prefill programs per 1,000 prompt tokens
they fed (trace: modules named ``jit_paged_prefill``; tokens: each
request admitted in the traced window, less its prompt's last token,
which the decode loop feeds; the engine prefills all of the rest in
chunks when it admits the request).  In ms."""

PREFILL_PROGRAM = "paged_prefill"


def read(run):
    if not run.trace:
        return None
    s, _ = run.trace.modules_matching(PREFILL_PROGRAM)
    tokens = sum(run.recs[rid].prompt_len - 1
                 for w in run.windows for rid in w.admitted)
    return 1e6 * s / tokens if s and tokens else None
