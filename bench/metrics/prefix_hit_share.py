"""Prompt tokens admitted in the window whose pages were mapped from
the prefix index: shared pages times the page size, over prompt
tokens admitted."""


def read(run):
    tokens = sum(sum(r.prefill_tokens) for r in run.rounds)
    if not tokens:
        return None
    pages = sum(r.shared_pages for r in run.rounds)
    return pages * run.conf["engine"]["page_size"] / tokens
