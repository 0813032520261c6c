"""Device time of local training per round in the lm cells: the reader of
``device.train_ms_per_round`` (the own time of the ops under the round
program's ``train`` named scope, per round), loaded by path. In a streamed
round that scope holds each row's training inside the row loop."""
import pathlib

from kinds import module

read = module(pathlib.Path(__file__).with_name(
    "device.train_ms_per_round.py")).read
