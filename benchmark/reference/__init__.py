"""Plain float32 reference of the architecture, and the scorers built on it.

Written from the published description of the Llama/Mistral decoder (RMSNorm,
grouped-query attention with rotary embeddings in the split-halves
convention, SwiGLU, untied head). Imports nothing of the program.
"""
