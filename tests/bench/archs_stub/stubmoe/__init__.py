"""A test-only architecture: sparse experts after leading dense layers,
with a sliding attention window, on the program's MoE family.  Put on the
lookup's path by ``test_bench_archs.py`` to show that a configuration of
another architecture comes in as one new package."""
ARCHITECTURES = ("StubMoeForCausalLM",)
