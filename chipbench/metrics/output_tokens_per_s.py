"""output_tokens_per_s: output tokens emitted inside the window over the
window's length."""
from chipbench.window import tokens_in


def read(run, name):
    return tokens_in(run.records, run.window) / run.window.seconds
