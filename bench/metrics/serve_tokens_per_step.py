"""Mean output tokens per token-yielding engine step, from the engine's
per-token time stamps (every token of one step carries the same stamp)."""


def read(rec):
    if rec["kind"] != "serve":
        return None
    return rec["tokens_per_step"]
