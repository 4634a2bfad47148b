"""A test-only package that claims a class ``bench/archs/qwen2`` claims."""
ARCHITECTURES = ("Qwen2ForCausalLM",)
