"""Prompt plus generated tokens of the requests that completed inside the
window, over the window: counted from the client's side, so a prefix cache
cannot shrink it.  Requests submitted in the lead-in count where they
complete in the window; the window closes at the end of the first engine step
that ends at or after ``--seconds``."""


def read(record, cell):
    raw = record["raw"]
    close = raw["closed_at"]
    tokens = sum(len(p.prompt) + len(p.handle.out_tokens)
                 for p in raw["requests"]
                 if p.finished is not None and 0.0 <= p.finished <= close)
    return tokens / close
