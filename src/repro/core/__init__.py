"""Core library: lattice graphs from cubic crystal lattices (the paper's
contribution), exact integer-matrix machinery, symmetry, routing, distance
analysis and throughput bounds."""
from . import intmat
from .condition import NetworkCondition
from .crystals import (BCC, FCC, PC, RTT, FourD_BCC, FourD_FCC, Lip, Torus,
                       bcc_matrix, boxplus, crystal_for_order, direct_sum,
                       fcc_matrix, fourd_bcc_matrix, fourd_fcc_matrix,
                       lip_matrix, nd_bcc_matrix, nd_fcc_matrix, nd_pc_matrix,
                       pc_matrix, rtt_matrix, torus_matrix, upgrade_path)
from .distances import (DistanceSummary, bcc_average_distance, bcc_diameter,
                        distance_stats, faulted_average_distance,
                        faulted_diameter, faulted_distance_matrix,
                        faulted_distance_profile, faulted_distance_sweep,
                        faulted_schedule_stats, fcc_average_distance,
                        fcc_diameter, mixed_torus_diameter,
                        pc_average_distance, pc_diameter, summarize,
                        torus_average_distance, weighted_average_distance,
                        weighted_diameter, weighted_distance_matrix)
from .fault_schedule import CompiledSchedule, FaultSchedule
from .lattice import InfeasibleNetwork, LatticeGraph
from .link_spec import LinkSpec
from .routing import (HierarchicalRouter, fault_aware_next_hop,
                      fault_aware_next_hop_device, make_router,
                      minimal_record_bruteforce, norm1, route_bcc, route_fcc,
                      route_ring, route_rtt, route_torus)
from .scenario import Scenario, scenario_connected
from .sim_config import SimConfig
try:
    from .routing_engine import RoutingEngine, credit_vc_select
except ImportError:           # jax absent — the numpy oracle stands alone
    RoutingEngine = None      # type: ignore[assignment,misc]
    credit_vc_select = None   # type: ignore[assignment]
from .symmetry import (bcc_lift_is_never_symmetric, is_linear_automorphism,
                       is_linearly_symmetric, linear_stabilizer,
                       signed_permutation_matrices,
                       theorem12_matrix_first_family,
                       theorem12_matrix_second_family)
from .throughput import (bcc_throughput_bound, channel_load,
                         channel_load_device, channel_load_stats,
                         channel_load_uniform, fault_aware_channel_load,
                         fault_aware_saturation_throughput,
                         fault_aware_schedule_load,
                         fault_aware_schedule_saturation,
                         fcc_throughput_bound, measured_saturation_throughput,
                         mixed_torus_throughput_bound, pc_throughput_bound,
                         saturation, symmetric_throughput_bound,
                         weighted_channel_load,
                         weighted_saturation_throughput)

__all__ = [
    "intmat", "LatticeGraph", "InfeasibleNetwork",
    "PC", "FCC", "BCC", "RTT", "Torus", "FourD_FCC", "FourD_BCC", "Lip",
    "pc_matrix", "fcc_matrix", "bcc_matrix", "rtt_matrix", "torus_matrix",
    "fourd_fcc_matrix", "fourd_bcc_matrix", "lip_matrix",
    "nd_pc_matrix", "nd_bcc_matrix", "nd_fcc_matrix",
    "boxplus", "direct_sum", "crystal_for_order", "upgrade_path",
    "route_ring", "route_torus", "route_rtt", "route_fcc", "route_bcc",
    "HierarchicalRouter", "RoutingEngine", "make_router",
    "minimal_record_bruteforce", "norm1",
    "pc_diameter", "fcc_diameter", "bcc_diameter", "mixed_torus_diameter",
    "pc_average_distance", "fcc_average_distance", "bcc_average_distance",
    "torus_average_distance", "summarize", "DistanceSummary",
    "signed_permutation_matrices", "is_linear_automorphism",
    "linear_stabilizer", "is_linearly_symmetric",
    "theorem12_matrix_first_family", "theorem12_matrix_second_family",
    "bcc_lift_is_never_symmetric",
    "symmetric_throughput_bound", "mixed_torus_throughput_bound",
    "pc_throughput_bound", "fcc_throughput_bound", "bcc_throughput_bound",
    "channel_load", "channel_load_device", "channel_load_uniform",
    "measured_saturation_throughput",
    "Scenario", "scenario_connected", "fault_aware_next_hop",
    "fault_aware_next_hop_device",
    "fault_aware_channel_load", "fault_aware_saturation_throughput",
    "faulted_distance_matrix", "faulted_distance_profile",
    "faulted_distance_sweep",
    "faulted_average_distance", "faulted_diameter",
    "FaultSchedule", "CompiledSchedule", "faulted_schedule_stats",
    "fault_aware_schedule_load", "fault_aware_schedule_saturation",
    "SimConfig", "credit_vc_select", "LinkSpec",
    "NetworkCondition", "distance_stats", "channel_load_stats", "saturation",
    "weighted_distance_matrix", "weighted_average_distance",
    "weighted_diameter", "weighted_channel_load",
    "weighted_saturation_throughput",
]
