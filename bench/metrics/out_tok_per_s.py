"""out_tok_per_s: output tokens the clients received in the window,
over the window's length."""


def read(run):
    return run.tokens_received() / run.seconds
