from repro.models.model import Model
from repro.scopes import SCOPES

__all__ = ["Model", "SCOPES"]
