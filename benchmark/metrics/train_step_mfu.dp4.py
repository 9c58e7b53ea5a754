"""The data-parallel training step's share of the peak of all the cell's
chips, in percent: ``train_step_mfu``'s own reading (``flops.
train_flops_per_token`` times the tokens of the steps finished in the traced
window — every chip's sequences — over the window and the bf16 peak times
the chips; the all-reduce is no operation of the model), under the name the
four-chip cell reports it by."""

from benchmark.run import load_reader

read = load_reader("train_step_mfu").read
