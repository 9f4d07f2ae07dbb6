# -*- coding: utf-8 -*-
# Generated by the protocol buffer compiler.  DO NOT EDIT!
# source: elasticdl_tpu.proto
"""Generated protocol buffer code."""
from google.protobuf.internal import builder as _builder
from google.protobuf import descriptor as _descriptor
from google.protobuf import descriptor_pool as _descriptor_pool
from google.protobuf import symbol_database as _symbol_database
# @@protoc_insertion_point(imports)

_sym_db = _symbol_database.Default()




DESCRIPTOR = _descriptor_pool.Default().AddSerializedFile(b'\n\x13\x65lasticdl_tpu.proto\x12\relasticdl_tpu\"\x07\n\x05\x45mpty\"\x93\x02\n\x04Task\x12\x0f\n\x07task_id\x18\x01 \x01(\x05\x12\x12\n\nshard_name\x18\x02 \x01(\t\x12\r\n\x05start\x18\x03 \x01(\x03\x12\x0b\n\x03\x65nd\x18\x04 \x01(\x03\x12%\n\x04type\x18\x05 \x01(\x0e\x32\x17.elasticdl_tpu.TaskType\x12\x15\n\rmodel_version\x18\x06 \x01(\x05\x12@\n\x0f\x65xtended_config\x18\x07 \x03(\x0b\x32\'.elasticdl_tpu.Task.ExtendedConfigEntry\x12\x13\n\x0blease_token\x18\x08 \x01(\x03\x1a\x35\n\x13\x45xtendedConfigEntry\x12\x0b\n\x03key\x18\x01 \x01(\t\x12\r\n\x05value\x18\x02 \x01(\t:\x02\x38\x01\"u\n\x06Tensor\x12\x0c\n\x04name\x18\x01 \x01(\t\x12\x0c\n\x04\x64ims\x18\x02 \x03(\x03\x12&\n\x05\x64type\x18\x03 \x01(\x0e\x32\x17.elasticdl_tpu.DataType\x12\x0f\n\x07\x63ontent\x18\x04 \x01(\x0c\x12\x16\n\x0estring_lengths\x18\x05 \x03(\x03\"^\n\rIndexedSlices\x12-\n\x0e\x63oncat_tensors\x18\x01 \x01(\x0b\x32\x15.elasticdl_tpu.Tensor\x12\x0b\n\x03ids\x18\x02 \x03(\x03\x12\x11\n\tids_bytes\x18\x03 \x01(\x0c\"l\n\x12\x45mbeddingTableInfo\x12\x0c\n\x04name\x18\x01 \x01(\t\x12\x0b\n\x03\x64im\x18\x02 \x01(\x03\x12\x13\n\x0binitializer\x18\x03 \x01(\t\x12&\n\x05\x64type\x18\x04 \x01(\x0e\x32\x17.elasticdl_tpu.DataType\"\xbd\x02\n\x05Model\x12\x0f\n\x07version\x18\x01 \x01(\x05\x12/\n\x10\x64\x65nse_parameters\x18\x02 \x03(\x0b\x32\x15.elasticdl_tpu.Tensor\x12\x43\n\x10\x65mbedding_tables\x18\x03 \x03(\x0b\x32).elasticdl_tpu.Model.EmbeddingTablesEntry\x12@\n\x15\x65mbedding_table_infos\x18\x04 \x03(\x0b\x32!.elasticdl_tpu.EmbeddingTableInfo\x12\x15\n\rtotal_records\x18\x05 \x01(\x03\x1aT\n\x14\x45mbeddingTablesEntry\x12\x0b\n\x03key\x18\x01 \x01(\t\x12+\n\x05value\x18\x02 \x01(\x0b\x32\x1c.elasticdl_tpu.IndexedSlices:\x02\x38\x01\"\x89\x01\n\x07\x45xample\x12\x36\n\x08\x66\x65\x61tures\x18\x01 \x03(\x0b\x32$.elasticdl_tpu.Example.FeaturesEntry\x1a\x46\n\rFeaturesEntry\x12\x0b\n\x03key\x18\x01 \x01(\t\x12$\n\x05value\x18\x02 \x01(\x0b\x32\x15.elasticdl_tpu.Tensor:\x02\x38\x01\"b\n\x0eGetTaskRequest\x12\x11\n\tworker_id\x18\x01 \x01(\x05\x12*\n\ttask_type\x18\x02 \x01(\x0e\x32\x17.elasticdl_tpu.TaskType\x12\x11\n\tmax_tasks\x18\x03 \x01(\x05\"\xda\x01\n\x17ReportTaskResultRequest\x12\x0f\n\x07task_id\x18\x01 \x01(\x05\x12\x13\n\x0b\x65rr_message\x18\x02 \x01(\t\x12O\n\rexec_counters\x18\x03 \x03(\x0b\x32\x38.elasticdl_tpu.ReportTaskResultRequest.ExecCountersEntry\x12\x13\n\x0blease_token\x18\x04 \x01(\x03\x1a\x33\n\x11\x45xecCountersEntry\x12\x0b\n\x03key\x18\x01 \x01(\t\x12\r\n\x05value\x18\x02 \x01(\x05:\x02\x38\x01\"\x88\x01\n\x1eReportEvaluationMetricsRequest\x12,\n\rmodel_outputs\x18\x01 \x03(\x0b\x32\x15.elasticdl_tpu.Tensor\x12%\n\x06labels\x18\x02 \x01(\x0b\x32\x15.elasticdl_tpu.Tensor\x12\x11\n\tworker_id\x18\x03 \x01(\x05\"-\n\x14ReportVersionRequest\x12\x15\n\rmodel_version\x18\x01 \x01(\x05\"G\n\x12GetCommRankRequest\x12\x13\n\x0bworker_host\x18\x01 \x01(\t\x12\x1c\n\x14ready_epoch_plus_one\x18\x02 \x01(\x05\"\x99\x01\n\x13GetCommRankResponse\x12\x0f\n\x07rank_id\x18\x01 \x01(\x05\x12\x12\n\nworld_size\x18\x02 \x01(\x05\x12\x15\n\rrendezvous_id\x18\x03 \x01(\x05\x12\x17\n\x0frendezvous_port\x18\x04 \x01(\x05\x12\x18\n\x10\x63oordinator_addr\x18\x05 \x01(\t\x12\x13\n\x0bworld_ready\x18\x06 \x01(\x08\">\n\x1bReportWorkerLivenessRequest\x12\x11\n\tworker_id\x18\x01 \x01(\x05\x12\x0c\n\x04host\x18\x02 \x01(\t\"O\n\x11LeaseStepsRequest\x12\x11\n\tworker_id\x18\x01 \x01(\x05\x12\x13\n\x0bworker_host\x18\x02 \x01(\t\x12\x12\n\nbatch_size\x18\x03 \x01(\x05\"<\n\nLeaseRange\x12\x12\n\nshard_name\x18\x01 \x01(\t\x12\r\n\x05start\x18\x02 \x01(\x03\x12\x0b\n\x03\x65nd\x18\x03 \x01(\x03\"\xf7\x01\n\x12LeaseStepsResponse\x12\x38\n\x06status\x18\x01 \x01(\x0e\x32(.elasticdl_tpu.LeaseStepsResponse.Status\x12\x10\n\x08lease_id\x18\x02 \x01(\x03\x12\r\n\x05\x65poch\x18\x03 \x01(\x05\x12\x0c\n\x04rank\x18\x04 \x01(\x05\x12\x12\n\nworld_size\x18\x05 \x01(\x05\x12\x0f\n\x07n_steps\x18\x06 \x01(\x05\x12)\n\x06ranges\x18\x07 \x03(\x0b\x32\x19.elasticdl_tpu.LeaseRange\"(\n\x06Status\x12\x06\n\x02OK\x10\x00\x12\x08\n\x04WAIT\x10\x01\x12\x0c\n\x08\x46INISHED\x10\x02\"m\n\x12ReportLeaseRequest\x12\x10\n\x08lease_id\x18\x01 \x01(\x03\x12\x11\n\tworker_id\x18\x02 \x01(\x05\x12\x0c\n\x04rank\x18\x03 \x01(\x05\x12\x0f\n\x07success\x18\x04 \x01(\x08\x12\x13\n\x0b\x65rr_message\x18\x05 \x01(\t\"\x15\n\x13GetJobStatusRequest\"\xb0\x07\n\x11JobStatusResponse\x12\x12\n\ntodo_tasks\x18\x01 \x01(\x05\x12\x13\n\x0b\x64oing_tasks\x18\x02 \x01(\x05\x12\r\n\x05\x65poch\x18\x03 \x01(\x05\x12\x12\n\nnum_epochs\x18\x04 \x01(\x05\x12\x15\n\rmodel_version\x18\x05 \x01(\x05\x12\x15\n\ralive_workers\x18\x06 \x01(\x05\x12\x10\n\x08\x66inished\x18\x07 \x01(\x08\x12\x12\n\njob_failed\x18\x08 \x01(\x08\x12\x14\n\x0crecords_done\x18\t \x01(\x03\x12P\n\x11last_eval_metrics\x18\n \x03(\x0b\x32\x35.elasticdl_tpu.JobStatusResponse.LastEvalMetricsEntry\x12\x19\n\x11last_eval_version\x18\x0b \x01(\x05\x12U\n\x14worker_last_seen_ago\x18\x0c \x03(\x0b\x32\x37.elasticdl_tpu.JobStatusResponse.WorkerLastSeenAgoEntry\x12R\n\x12worker_doing_tasks\x18\r \x03(\x0b\x32\x36.elasticdl_tpu.JobStatusResponse.WorkerDoingTasksEntry\x12\x12\n\nrelaunches\x18\x0e \x01(\x05\x12\x17\n\x0ftasks_recovered\x18\x0f \x01(\x03\x12\x14\n\x0cmetrics_port\x18\x10 \x01(\x05\x12\x18\n\x10membership_epoch\x18\x11 \x01(\x05\x12\x17\n\x0ftasks_abandoned\x18\x12 \x01(\x03\x12\x12\n\nstragglers\x18\x13 \x03(\t\x12\x14\n\x0c\x61lerts_fired\x18\x14 \x01(\x03\x12\x16\n\x0epolicy_actions\x18\x15 \x01(\x03\x12\x1a\n\x12policy_blacklisted\x18\x16 \x03(\t\x12\x1d\n\x15\x62\x61\x63kup_tasks_inflight\x18\x17 \x01(\x05\x12\x13\n\x0b\x62\x61\x63kup_wins\x18\x18 \x01(\x03\x12\x1a\n\x12master_incarnation\x18\x19 \x01(\x03\x1a\x36\n\x14LastEvalMetricsEntry\x12\x0b\n\x03key\x18\x01 \x01(\t\x12\r\n\x05value\x18\x02 \x01(\x02:\x02\x38\x01\x1a\x38\n\x16WorkerLastSeenAgoEntry\x12\x0b\n\x03key\x18\x01 \x01(\x05\x12\r\n\x05value\x18\x02 \x01(\x02:\x02\x38\x01\x1a\x37\n\x15WorkerDoingTasksEntry\x12\x0b\n\x03key\x18\x01 \x01(\x05\x12\r\n\x05value\x18\x02 \x01(\x05:\x02\x38\x01\";\n\x13StartProfileRequest\x12\x0f\n\x07seconds\x18\x01 \x01(\x02\x12\x13\n\x0brole_prefix\x18\x02 \x01(\t\">\n\x14StartProfileResponse\x12\x10\n\x08\x63\x61ptured\x18\x01 \x01(\x05\x12\x14\n\x0cresults_json\x18\x02 \x01(\t\"Z\n\x11TelemetrySnapshot\x12\x0c\n\x04role\x18\x01 \x01(\t\x12\x0b\n\x03pid\x18\x02 \x01(\x03\x12\x0b\n\x03seq\x18\x03 \x01(\x03\x12\x0c\n\x04\x66ull\x18\x04 \x01(\x08\x12\x0f\n\x07payload\x18\x05 \x01(\t\"]\n\x16ReportTelemetryRequest\x12\x33\n\tsnapshots\x18\x01 \x03(\x0b\x32 .elasticdl_tpu.TelemetrySnapshot\x12\x0e\n\x06origin\x18\x02 \x01(\t\">\n\x17ReportTelemetryResponse\x12\x10\n\x08\x61\x63\x63\x65pted\x18\x01 \x01(\x05\x12\x11\n\tneed_full\x18\x02 \x03(\t\"A\n\tTaskBatch\x12\"\n\x05tasks\x18\x01 \x03(\x0b\x32\x13.elasticdl_tpu.Task\x12\x10\n\x08\x66inished\x18\x02 \x01(\x08\"S\n\x18ReportTaskResultsRequest\x12\x37\n\x07results\x18\x01 \x03(\x0b\x32&.elasticdl_tpu.ReportTaskResultRequest\"(\n\x13GetWorldHintRequest\x12\x11\n\tworker_id\x18\x01 \x01(\x05\"e\n\x11WorldHintResponse\x12\x10\n\x08hint_seq\x18\x01 \x01(\x03\x12\x19\n\x11target_world_size\x18\x02 \x01(\x05\x12\x0e\n\x06reason\x18\x03 \x01(\t\x12\x13\n\x0b\x61ge_seconds\x18\x04 \x01(\x02\"-\n\x1aPullDenseParametersRequest\x12\x0f\n\x07version\x18\x01 \x01(\x05\"t\n\x1bPullDenseParametersResponse\x12\x13\n\x0binitialized\x18\x01 \x01(\x08\x12\x0f\n\x07version\x18\x02 \x01(\x05\x12/\n\x10\x64\x65nse_parameters\x18\x03 \x03(\x0b\x32\x15.elasticdl_tpu.Tensor\"y\n\x1bPullEmbeddingVectorsRequest\x12\x0c\n\x04name\x18\x01 \x01(\t\x12\x0b\n\x03ids\x18\x02 \x03(\x03\x12\x11\n\tids_bytes\x18\x03 \x01(\x0c\x12,\n\x0bvalue_dtype\x18\x04 \x01(\x0e\x32\x17.elasticdl_tpu.DataType\"N\n\x19PullEmbeddingTableRequest\x12\x0c\n\x04name\x18\x01 \x01(\t\x12\x11\n\tstart_row\x18\x02 \x01(\x03\x12\x10\n\x08max_rows\x18\x03 \x01(\x03\"\x86\x01\n\x14PushGradientsRequest\x12\'\n\tgradients\x18\x01 \x01(\x0b\x32\x14.elasticdl_tpu.Model\x12\x15\n\rlearning_rate\x18\x02 \x01(\x02\x12\x1a\n\x12worker_id_plus_one\x18\x03 \x01(\x05\x12\x12\n\nbatch_size\x18\x04 \x01(\x05\"Q\n\x15PushGradientsResponse\x12\x10\n\x08\x61\x63\x63\x65pted\x18\x01 \x01(\x08\x12\x0f\n\x07version\x18\x02 \x01(\x05\x12\x15\n\rapply_seconds\x18\x03 \x01(\x02\"\xb2\x01\n\nTensorSpan\x12\x0c\n\x04name\x18\x01 \x01(\t\x12\x0c\n\x04\x64ims\x18\x02 \x03(\x03\x12&\n\x05\x64type\x18\x03 \x01(\x0e\x32\x17.elasticdl_tpu.DataType\x12\x0e\n\x06offset\x18\x04 \x01(\x03\x12\x0e\n\x06nbytes\x18\x05 \x01(\x03\x12\x15\n\rscales_offset\x18\x06 \x01(\x03\x12\x15\n\rscales_nbytes\x18\x07 \x01(\x03\x12\x12\n\nblock_size\x18\x08 \x01(\x05\"_\n\nSlicesSpan\x12)\n\x06values\x18\x01 \x01(\x0b\x32\x19.elasticdl_tpu.TensorSpan\x12\x12\n\nids_offset\x18\x02 \x01(\x03\x12\x12\n\nids_nbytes\x18\x03 \x01(\x03\"\xca\x02\n\x1aPushGradientsPackedRequest\x12\x0f\n\x07version\x18\x01 \x01(\x05\x12\x15\n\rlearning_rate\x18\x02 \x01(\x02\x12\x1a\n\x12worker_id_plus_one\x18\x03 \x01(\x05\x12\x12\n\nbatch_size\x18\x04 \x01(\x05\x12(\n\x05\x64\x65nse\x18\x05 \x03(\x0b\x32\x19.elasticdl_tpu.TensorSpan\x12)\n\x06sparse\x18\x06 \x03(\x0b\x32\x19.elasticdl_tpu.SlicesSpan\x12\x0f\n\x07push_id\x18\x07 \x01(\x04\x12\x13\n\x0b\x63hunk_index\x18\x08 \x01(\x05\x12\x13\n\x0b\x63hunk_count\x18\t \x01(\x05\x12\x16\n\x0epayload_offset\x18\n \x01(\x03\x12\x1b\n\x13payload_total_bytes\x18\x0b \x01(\x03\x12\x0f\n\x07payload\x18\x0c \x01(\x0c*Z\n\x08TaskType\x12\x0c\n\x08TRAINING\x10\x00\x12\x0e\n\nEVALUATION\x10\x01\x12\x0e\n\nPREDICTION\x10\x02\x12\x08\n\x04WAIT\x10\x03\x12\x16\n\x12TRAIN_END_CALLBACK\x10\x04*\xe8\x01\n\x08\x44\x61taType\x12\x0e\n\nDT_INVALID\x10\x00\x12\x0e\n\nDT_FLOAT32\x10\x01\x12\x0e\n\nDT_FLOAT64\x10\x02\x12\x0e\n\nDT_FLOAT16\x10\x03\x12\x0f\n\x0b\x44T_BFLOAT16\x10\x04\x12\x0b\n\x07\x44T_INT8\x10\x05\x12\x0c\n\x08\x44T_INT16\x10\x06\x12\x0c\n\x08\x44T_INT32\x10\x07\x12\x0c\n\x08\x44T_INT64\x10\x08\x12\x0c\n\x08\x44T_UINT8\x10\t\x12\r\n\tDT_UINT32\x10\n\x12\r\n\tDT_UINT64\x10\x0b\x12\x0b\n\x07\x44T_BOOL\x10\x0c\x12\r\n\tDT_STRING\x10\r\x12\x0c\n\x08\x44T_BYTES\x10\x0e\x62\x06proto3')

_builder.BuildMessageAndEnumDescriptors(DESCRIPTOR, globals())
_builder.BuildTopDescriptorsAndMessages(DESCRIPTOR, 'elasticdl_tpu_pb2', globals())
if _descriptor._USE_C_DESCRIPTORS == False:

  DESCRIPTOR._options = None
  _TASK_EXTENDEDCONFIGENTRY._options = None
  _TASK_EXTENDEDCONFIGENTRY._serialized_options = b'8\001'
  _MODEL_EMBEDDINGTABLESENTRY._options = None
  _MODEL_EMBEDDINGTABLESENTRY._serialized_options = b'8\001'
  _EXAMPLE_FEATURESENTRY._options = None
  _EXAMPLE_FEATURESENTRY._serialized_options = b'8\001'
  _REPORTTASKRESULTREQUEST_EXECCOUNTERSENTRY._options = None
  _REPORTTASKRESULTREQUEST_EXECCOUNTERSENTRY._serialized_options = b'8\001'
  _JOBSTATUSRESPONSE_LASTEVALMETRICSENTRY._options = None
  _JOBSTATUSRESPONSE_LASTEVALMETRICSENTRY._serialized_options = b'8\001'
  _JOBSTATUSRESPONSE_WORKERLASTSEENAGOENTRY._options = None
  _JOBSTATUSRESPONSE_WORKERLASTSEENAGOENTRY._serialized_options = b'8\001'
  _JOBSTATUSRESPONSE_WORKERDOINGTASKSENTRY._options = None
  _JOBSTATUSRESPONSE_WORKERDOINGTASKSENTRY._serialized_options = b'8\001'
  _TASKTYPE._serialized_start=5256
  _TASKTYPE._serialized_end=5346
  _DATATYPE._serialized_start=5349
  _DATATYPE._serialized_end=5581
  _EMPTY._serialized_start=38
  _EMPTY._serialized_end=45
  _TASK._serialized_start=48
  _TASK._serialized_end=323
  _TASK_EXTENDEDCONFIGENTRY._serialized_start=270
  _TASK_EXTENDEDCONFIGENTRY._serialized_end=323
  _TENSOR._serialized_start=325
  _TENSOR._serialized_end=442
  _INDEXEDSLICES._serialized_start=444
  _INDEXEDSLICES._serialized_end=538
  _EMBEDDINGTABLEINFO._serialized_start=540
  _EMBEDDINGTABLEINFO._serialized_end=648
  _MODEL._serialized_start=651
  _MODEL._serialized_end=968
  _MODEL_EMBEDDINGTABLESENTRY._serialized_start=884
  _MODEL_EMBEDDINGTABLESENTRY._serialized_end=968
  _EXAMPLE._serialized_start=971
  _EXAMPLE._serialized_end=1108
  _EXAMPLE_FEATURESENTRY._serialized_start=1038
  _EXAMPLE_FEATURESENTRY._serialized_end=1108
  _GETTASKREQUEST._serialized_start=1110
  _GETTASKREQUEST._serialized_end=1208
  _REPORTTASKRESULTREQUEST._serialized_start=1211
  _REPORTTASKRESULTREQUEST._serialized_end=1429
  _REPORTTASKRESULTREQUEST_EXECCOUNTERSENTRY._serialized_start=1378
  _REPORTTASKRESULTREQUEST_EXECCOUNTERSENTRY._serialized_end=1429
  _REPORTEVALUATIONMETRICSREQUEST._serialized_start=1432
  _REPORTEVALUATIONMETRICSREQUEST._serialized_end=1568
  _REPORTVERSIONREQUEST._serialized_start=1570
  _REPORTVERSIONREQUEST._serialized_end=1615
  _GETCOMMRANKREQUEST._serialized_start=1617
  _GETCOMMRANKREQUEST._serialized_end=1688
  _GETCOMMRANKRESPONSE._serialized_start=1691
  _GETCOMMRANKRESPONSE._serialized_end=1844
  _REPORTWORKERLIVENESSREQUEST._serialized_start=1846
  _REPORTWORKERLIVENESSREQUEST._serialized_end=1908
  _LEASESTEPSREQUEST._serialized_start=1910
  _LEASESTEPSREQUEST._serialized_end=1989
  _LEASERANGE._serialized_start=1991
  _LEASERANGE._serialized_end=2051
  _LEASESTEPSRESPONSE._serialized_start=2054
  _LEASESTEPSRESPONSE._serialized_end=2301
  _LEASESTEPSRESPONSE_STATUS._serialized_start=2261
  _LEASESTEPSRESPONSE_STATUS._serialized_end=2301
  _REPORTLEASEREQUEST._serialized_start=2303
  _REPORTLEASEREQUEST._serialized_end=2412
  _GETJOBSTATUSREQUEST._serialized_start=2414
  _GETJOBSTATUSREQUEST._serialized_end=2435
  _JOBSTATUSRESPONSE._serialized_start=2438
  _JOBSTATUSRESPONSE._serialized_end=3382
  _JOBSTATUSRESPONSE_LASTEVALMETRICSENTRY._serialized_start=3213
  _JOBSTATUSRESPONSE_LASTEVALMETRICSENTRY._serialized_end=3267
  _JOBSTATUSRESPONSE_WORKERLASTSEENAGOENTRY._serialized_start=3269
  _JOBSTATUSRESPONSE_WORKERLASTSEENAGOENTRY._serialized_end=3325
  _JOBSTATUSRESPONSE_WORKERDOINGTASKSENTRY._serialized_start=3327
  _JOBSTATUSRESPONSE_WORKERDOINGTASKSENTRY._serialized_end=3382
  _STARTPROFILEREQUEST._serialized_start=3384
  _STARTPROFILEREQUEST._serialized_end=3443
  _STARTPROFILERESPONSE._serialized_start=3445
  _STARTPROFILERESPONSE._serialized_end=3507
  _TELEMETRYSNAPSHOT._serialized_start=3509
  _TELEMETRYSNAPSHOT._serialized_end=3599
  _REPORTTELEMETRYREQUEST._serialized_start=3601
  _REPORTTELEMETRYREQUEST._serialized_end=3694
  _REPORTTELEMETRYRESPONSE._serialized_start=3696
  _REPORTTELEMETRYRESPONSE._serialized_end=3758
  _TASKBATCH._serialized_start=3760
  _TASKBATCH._serialized_end=3825
  _REPORTTASKRESULTSREQUEST._serialized_start=3827
  _REPORTTASKRESULTSREQUEST._serialized_end=3910
  _GETWORLDHINTREQUEST._serialized_start=3912
  _GETWORLDHINTREQUEST._serialized_end=3952
  _WORLDHINTRESPONSE._serialized_start=3954
  _WORLDHINTRESPONSE._serialized_end=4055
  _PULLDENSEPARAMETERSREQUEST._serialized_start=4057
  _PULLDENSEPARAMETERSREQUEST._serialized_end=4102
  _PULLDENSEPARAMETERSRESPONSE._serialized_start=4104
  _PULLDENSEPARAMETERSRESPONSE._serialized_end=4220
  _PULLEMBEDDINGVECTORSREQUEST._serialized_start=4222
  _PULLEMBEDDINGVECTORSREQUEST._serialized_end=4343
  _PULLEMBEDDINGTABLEREQUEST._serialized_start=4345
  _PULLEMBEDDINGTABLEREQUEST._serialized_end=4423
  _PUSHGRADIENTSREQUEST._serialized_start=4426
  _PUSHGRADIENTSREQUEST._serialized_end=4560
  _PUSHGRADIENTSRESPONSE._serialized_start=4562
  _PUSHGRADIENTSRESPONSE._serialized_end=4643
  _TENSORSPAN._serialized_start=4646
  _TENSORSPAN._serialized_end=4824
  _SLICESSPAN._serialized_start=4826
  _SLICESSPAN._serialized_end=4921
  _PUSHGRADIENTSPACKEDREQUEST._serialized_start=4924
  _PUSHGRADIENTSPACKEDREQUEST._serialized_end=5254
# @@protoc_insertion_point(module_scope)
