from .sharding import (ParallelContext, TPShard, constraint, from_mesh,
                       resolve_spec, tree_shardings)
