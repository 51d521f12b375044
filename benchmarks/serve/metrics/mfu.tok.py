"""Whole step: the operations the window's work needs over the window
times the chip's peak bf16 FLOP/s, in %.  The work is every prefill
whose first token reached its client in the window and every decoded
token that did (``Run.needed_flops``, counted from the configuration:
2 per multiply-add of every weight matrix, the output head only where
a token is chosen, and causal attention over the keys each query
sees)."""


def read(run):
    if not run.peaks:
        return None
    return 100.0 * run.needed_flops() / (run.seconds
                                         * run.peaks["bf16_flops"])
