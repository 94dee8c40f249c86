from repro.serve.stencil import (  # noqa: F401
    Frame,
    RequestHandle,
    StencilEngine,
    StencilEngineConfig,
    StencilRequest,
)
