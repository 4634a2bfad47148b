"""Qwen2 decoder (Qwen1.5 and Qwen2.5): full causal attention with q/k/v
biases and grouped KV heads, a SiLU-gated FFN, a tied or untied head."""
ARCHITECTURES = ("Qwen2ForCausalLM",)
