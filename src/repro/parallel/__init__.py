from . import sharding
from .sharding import (activation_rules, constrain, make_activation_rules,
                       make_param_specs, named_tree)
