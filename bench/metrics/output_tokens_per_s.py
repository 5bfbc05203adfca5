"""Output tokens per second: every token served to a finished request over
the whole measured serve, from its start until the last request finishes
(host clock)."""


def read(run):
    n = sum(len(r.output_tokens) for r in run.done)
    return n / run.window_s if n else None
