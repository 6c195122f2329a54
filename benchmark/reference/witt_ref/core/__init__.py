from .params import WParameters, protocol_registry, register_protocol

__all__ = ["WParameters", "protocol_registry", "register_protocol"]
