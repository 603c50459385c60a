"""Share of prompt positions served from the prefix cache: prefill tokens
skipped over skipped plus computed, in %. KV cache manager layer."""


def read(run):
    m = run.engine_metrics
    skipped = m.get("prefill_tokens_skipped", 0)
    total = skipped + m.get("prefill_tokens_computed", 0)
    return 100.0 * skipped / total if total else None
