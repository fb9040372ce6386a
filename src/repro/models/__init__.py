from .config import ModelConfig, LayerSpec, layer_plan, scan_plan
from .transformer import (KV_BLOCK, blocks_for, encode, forward, init_caches,
                          init_params)
from .frontend import (fake_frontend_embed, frontend_embed_shape,
                       frontend_embed_spec)
