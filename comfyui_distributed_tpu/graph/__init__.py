"""Workflow graph layer: JSON prompt graphs, node registry, executor.

The framework's equivalent of ComfyUI's prompt/executor surface that
the reference is parasitic on (reference SURVEY: "no standalone
runtime ... parasitic on ComfyUI's PromptServer"). Here it is a
standalone component: prompt graphs use the same JSON shape as
ComfyUI API prompts ({id: {class_type, inputs}}, links as
[node_id, output_index]) so the reference's bundled workflows port
directly, but execution compiles onto JAX.
"""

from .executor import ExecutionContext, GraphExecutor, validate_prompt  # noqa: F401
from .prompt import PromptIndex  # noqa: F401
from .registry import NODE_REGISTRY, register_node  # noqa: F401

# Importing the node modules registers the node classes.
from . import nodes_core  # noqa: F401,E402
from . import nodes_distributed  # noqa: F401,E402
from . import nodes_upscale  # noqa: F401,E402
from . import nodes_video  # noqa: F401,E402
from . import nodes_audio  # noqa: F401,E402
from . import nodes_controlnet  # noqa: F401,E402
from . import nodes_mask  # noqa: F401,E402
from . import nodes_custom_sampling  # noqa: F401,E402
from . import nodes_loaders  # noqa: F401,E402
from . import nodes_transform  # noqa: F401,E402
from . import nodes_text  # noqa: F401,E402
