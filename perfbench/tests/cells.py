"""Cells the tests drive that ``BENCHMARK.json`` does not hold yet."""

# The four-chip cell is not in BENCHMARK.json yet (PERF.md, Open questions:
# the chip budget of PR 24 did not reach it). Its traffic file and the
# drivers' and the reference's several-replica path are here for the PR that
# adds it, and the tests keep them alive through this entry.
DP4 = {
    "name": "resnet18-b16384-dp4", "config": "resnet18-cifar", "traffic": "cifar-fit-b16384", "chips": 4,
    "why": "Trainer.fit() on the 2x2: 4096 images a chip, 16384 a step; the gradient exchange exists only across chips",
}
