"""Device scope names inside a forward (``jax.named_scope``). They land in
the compiled ops' ``op_name`` metadata under the serving step's own scopes
(``serving/spans.py``, which lists them as ``LAYER_SCOPES``)."""

LATENT_ATTENTION = "latent_attention"  # MLA absorbed over the latent pages
MOE_ROUTE = "moe_route"        # the router's softmax and top-k
MOE_EXPERTS = "moe_experts"    # the held experts' grouped matmuls + shared
