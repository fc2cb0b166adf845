from repro.configs.base import (
    ArchConfig, MoEConfig, MLAConfig, SSMConfig, RWKVConfig, YarnConfig,
    EncDecConfig, HybridConfig, ShapeConfig, SHAPES,
)
from repro.configs.registry import (
    arch_ids, get_arch, get_shape, all_cells, cell_is_runnable, share_ids,
)
